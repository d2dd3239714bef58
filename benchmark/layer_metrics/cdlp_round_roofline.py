"""Kernels (``models/cdlp.py``): percent of the chip's memory roofline a
round of CDLP reaches, from the device trace: the bytes a round has to
move (``kernels/cdlp_round.py``, from the served graph's vertices and
directed edge slots alone, whatever implements the round: the sort's own
traffic is not in it) over the device-busy seconds a round takes times
the device's peak HBM bandwidth (``peaks.json``, by ``device_kind``; a
device that is not in the table is an error). The busy seconds: the busy
share of the traced slice over the run's jobs a second, over the rounds
the mix asks of a job. Host time inside a job is not in it; every device
operation of a job is charged to its rounds. Nothing without a trace in
which the device ran, an answered job or the graph's counts."""

import files
import stats


def read(record: dict):
    trace, graph = record.get("trace"), record.get("graph")
    if not trace or not trace["busy_s"] or not graph \
            or not stats.answered(record):
        return None
    rounds = int(record["mix"]["request"]["body"]["iterations"])
    round_s = trace["busy_s"] / trace["window_s"] \
        / stats.throughput(record) / rounds
    import jax

    kind = jax.devices()[0].device_kind
    peak = files.load_json("peaks.json")["devices"][kind]
    nbytes = files.load_module("kernels", "cdlp_round").count(graph)["bytes"]
    return 100.0 * nbytes / (round_s * float(peak["hbm_bytes_per_s"]))
