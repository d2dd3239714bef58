"""Kernels (compile cache): seconds of set-up spent compiling: the
``compile`` spans before the window that the persistent cache did not
have (``miss``) or was not asked for (``off``), summed. Prints the keys
that took most of it."""

import spans


def read(record: dict):
    return spans.read_warm(record, ("miss", "off"), "compiled")
