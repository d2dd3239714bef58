"""What one round of CDLP has to move, counted from the graph alone — so
it reads the same work whatever implements the round: every directed edge
slot's neighbour id read and that neighbour's label gathered (4 + 4
bytes), and a vertex's label read and its new label written (8 bytes).
Grouping the labels (a sort's passes, a scan's) is the implementation's
traffic, not the algorithm's, and is not in the count. A compare an edge
slot and a few a vertex: bandwidth bounds it."""


def count(shapes: dict) -> dict:
    slots, n = int(shapes["edge_slots"]), int(shapes["n"])
    return {"ops": 2 * slots + 2 * n, "bytes": 8 * slots + 8 * n}
