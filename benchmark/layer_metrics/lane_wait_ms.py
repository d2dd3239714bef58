"""Interactive lane (``olap/serving/interactive``): median of the
``/traverse`` response's ``wait_ms`` over the answered queries."""

import stats


def read(record: dict):
    values = stats.field(record, "wait_ms")
    return stats.median(values) if values else None
