"""Snapshot (``olap/tpu/snapshot.py``, ``bfs_hybrid.build_chunked_csr``):
the benchmark's clock round ``from_arrays`` and the first
``build_chunked_csr`` — host layout plus upload."""


def read(record: dict):
    return record["setup"].get("snapshot_s")
