"""Kernels (compile cache): seconds of set-up spent loading executables
from the persistent cache: the ``compile`` spans before the window with
``cache`` = ``hit``, summed. Prints the keys that took most of it."""

import spans


def read(record: dict):
    return spans.read_warm(record, ("hit",), "loaded")
