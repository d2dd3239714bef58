"""Kernels (``frontier.frontier_wcc``): percent of the chip's memory
roofline a WCC job reaches, from the device trace: the bytes a job has to
move (``kernels/wcc_job.py``, from the served graph's vertices and
directed edge slots alone) over the device-busy seconds a job takes
(``device_ms_per_req``: the busy share of the traced slice over the run's
jobs a second) times the device's peak HBM bandwidth (``peaks.json``, by
``device_kind``; a device that is not in the table is an error). Host
time inside a job is not in it; every device operation of a job is
charged to it. Nothing without a trace in which the device ran, an
answered job or the graph's counts."""

import files
import stats


def read(record: dict):
    trace, graph = record.get("trace"), record.get("graph")
    if not trace or not trace["busy_s"] or not graph \
            or not stats.answered(record):
        return None
    job_ms = files.load_module("layer_metrics",
                               "device_ms_per_req").read(record)
    import jax

    kind = jax.devices()[0].device_kind
    peak = files.load_json("peaks.json")["devices"][kind]
    nbytes = files.load_module("kernels", "wcc_job").count(graph)["bytes"]
    return files.load_module("layer_metrics", "pr_iter_roofline").share(
        nbytes, job_ms, float(peak["hbm_bytes_per_s"]))
