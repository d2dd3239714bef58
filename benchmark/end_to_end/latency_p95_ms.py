"""95th percentile of the same client latencies as ``latency_p50_ms``;
only for cells with some hundreds of requests a window."""

import stats


def read(record: dict):
    return stats.percentile(stats.latencies_ms(record), 95.0)
