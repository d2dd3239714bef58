"""Kernels (``frontier.pagerank_dense``): percent of the chip's memory
roofline an iteration reaches, from the device trace: the bytes it has
to move (``kernels/pagerank_iteration.py``, from the served graph's
vertices and directed edge slots alone) over the device-busy seconds an
iteration takes times the device's peak HBM bandwidth (``peaks.json``,
by ``device_kind``; a device that is not in the table is an error). The
busy seconds: ``device_ms_per_req`` (the busy share of the traced slice
over the run's jobs a second) over the iterations the mix asks of a job.
Host time inside a job is not in it. Nothing without a trace in which
the device ran, an answered job or the graph's counts."""

import files
import stats


def share(nbytes: float, iter_ms: float, bytes_per_s: float) -> float:
    return 100.0 * nbytes / (iter_ms / 1e3 * bytes_per_s)


def read(record: dict):
    trace, graph = record.get("trace"), record.get("graph")
    if not trace or not trace["busy_s"] or not graph \
            or not stats.answered(record):
        return None
    job_ms = files.load_module("layer_metrics",
                               "device_ms_per_req").read(record)
    iterations = int(record["mix"]["request"]["body"]["iterations"])
    import jax

    kind = jax.devices()[0].device_kind
    peak = files.load_json("peaks.json")["devices"][kind]
    nbytes = files.load_module("kernels", "pagerank_iteration") \
        .count(graph)["bytes"]
    return share(nbytes, job_ms / iterations, float(peak["hbm_bytes_per_s"]))
