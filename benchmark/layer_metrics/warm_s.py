"""Kernels (compile cache): the benchmark's clock round the warm-up
requests — cache loads or compiles, plus one pass of every shape."""


def read(record: dict):
    return record["setup"].get("warmup_s")
