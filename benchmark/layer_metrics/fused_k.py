"""Interactive lane (``olap/serving/interactive``): mean of the
``/traverse`` response's ``fused_k`` over the answered queries."""

import stats


def read(record: dict):
    values = stats.field(record, "fused_k")
    return stats.mean(values) if values else None
