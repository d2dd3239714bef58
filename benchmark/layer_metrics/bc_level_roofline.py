"""Kernels (``models/bc.py``): percent of the chip's memory roofline a
level of a BC job reaches, from the device trace: the bytes a level has
to move (``kernels/bc_level.py``, from the served graph's vertices and
directed edge slots alone, whatever implements the level) over the
device-busy seconds a level takes times the device's peak HBM bandwidth
(``peaks.json``, by ``device_kind``; a device that is not in the table is
an error). The busy seconds: the busy share of the traced slice over the
run's jobs a second, over the level programs a job dispatched (the median
count of its ``bc_forward_level`` and ``bc_backward_level`` ``kernel``
spans, ``bc_pull_ms``'s list). Host time inside a job is not in it; every
device operation of a job (the seeds, the result) is charged to its
levels. Nothing without a trace in which the device ran, an answered job,
the graph's counts or the level programs' spans."""

import files
import stats


def read(record: dict):
    trace, graph = record.get("trace"), record.get("graph")
    if not trace or not trace["busy_s"] or not graph \
            or not stats.answered(record):
        return None
    per_job = files.load_module("layer_metrics", "bc_pull_ms").calls(record)
    if per_job is None:
        return None
    levels = stats.median([len(job) for job in per_job])
    level_s = trace["busy_s"] / trace["window_s"] \
        / stats.throughput(record) / levels
    import jax

    kind = jax.devices()[0].device_kind
    peak = files.load_json("peaks.json")["devices"][kind]
    nbytes = files.load_module("kernels", "bc_level").count(graph)["bytes"]
    return 100.0 * nbytes / (level_s * float(peak["hbm_bytes_per_s"]))
