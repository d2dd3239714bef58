"""Median client latency: request due (open loop) or sent (closed loop)
to the checked answer in the client's hands."""

import stats


def read(record: dict):
    return stats.median(stats.latencies_ms(record))
