"""Process start to the first timed request: graph, snapshot, upload,
server, warm-up (and compilation, in a run that compiles). Time the
parent spent only waiting for the plain reference is not set-up."""


def read(record: dict):
    return record["setup"]["total_s"]
