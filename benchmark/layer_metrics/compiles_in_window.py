"""Kernels (``utils/jitcache.py`` via ``obs/devprof.py``): executables
the program built between the window's start and the drain. A hit in the
persistent cache counts — it still stalls a batch."""


def read(record: dict):
    return record.get("compiles")
