"""Kernels (``models/bfs_hybrid.py``): percent of the chip's memory
roofline a BFS job reaches, from the device trace: the bytes a job has to
move (``kernels/bfs_job.py``, from the served graph's vertices and
directed edge slots alone, whatever implements the job: bitmaps, candidate
lists and n-wide plans are not in it) over the device-busy seconds a job
takes times the device's peak HBM bandwidth (``peaks.json``, by
``device_kind``; a device that is not in the table is an error). The busy
seconds: the busy share of the traced slice over the run's jobs a second,
as ``lcc_job_roofline`` reads them. Host time inside a job is not in it;
every device operation of a job is charged to it. It bounds a claim and
ranks nothing. Nothing without a trace in which the device ran, an
answered job or the graph's counts."""

import files
import stats


def read(record: dict):
    trace, graph = record.get("trace"), record.get("graph")
    if not trace or not trace["busy_s"] or not graph \
            or not stats.answered(record):
        return None
    job_s = trace["busy_s"] / trace["window_s"] / stats.throughput(record)
    import jax

    kind = jax.devices()[0].device_kind
    peak = files.load_json("peaks.json")["devices"][kind]
    nbytes = files.load_module("kernels", "bfs_job").count(graph)["bytes"]
    return 100.0 * nbytes / (job_s * float(peak["hbm_bytes_per_s"]))
