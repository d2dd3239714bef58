"""Wire (``server.py``): median time of ``GET /jobs/<id>/result/<name>``,
request sent -> the array's bytes in the client's hands."""

import stats


def read(record: dict):
    values = stats.field(record, "fetch_ms")
    return stats.median(values) if values else None
