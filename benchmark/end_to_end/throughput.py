"""Checked answers per second, window start to last completion."""

import stats


def read(record: dict):
    return stats.throughput(record)
