"""Interactive lane (``olap/serving/interactive``): median of the
``/traverse`` response's ``exec_ms`` over the answered queries."""

import stats


def read(record: dict):
    values = stats.field(record, "exec_ms")
    return stats.median(values) if values else None
