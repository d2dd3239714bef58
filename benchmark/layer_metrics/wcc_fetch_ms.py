"""Wire (``server.py``): median time of ``GET /jobs/<id>/result/labels``,
request sent -> all ``n`` int32 labels (35.5 MB at graph500-24, one
body) in the client's hands: ``result_fetch_ms``'s reading, under the
name this cell lists."""

import files


def read(record: dict):
    return files.load_module("layer_metrics", "result_fetch_ms").read(record)
