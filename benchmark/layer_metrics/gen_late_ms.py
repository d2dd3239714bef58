"""Load generator (benchmark): 95th percentile of how late a request
left, against when it was due. Open loop only: a closed-loop request is
due when it is sent."""

import stats


def read(record: dict):
    late = [(s["sent"] - s["due"]) * 1e3 for s in record["samples"]
            if s["sent"] != s["due"]]
    return stats.percentile(late, 95.0) if late else None
