"""Interactive lane (``olap/serving/interactive``): percent of the window
(start to last completion) in which the lane's worker was executing a
batch: the union of the ``interactive`` root spans, clipped to it."""

import spans

LEAD_S = 60.0       # a batch that began before the window reaches into it


def read(record: dict):
    got = spans.in_window(record, lead_s=LEAD_S)
    if got is None:
        return None
    w = record["window"]
    return spans.busy_share(got, w["start"], w["last_done"])
