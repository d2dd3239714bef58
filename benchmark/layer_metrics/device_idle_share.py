"""Device: share of the traced slice in which no operation ran on the
chip (1 - union of op intervals / slice), in percent."""


def read(record: dict):
    trace = record.get("trace")
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
