"""What one LCC job has to move, counted from the graph alone — so it
reads the same work whatever implements it (a hub bit table, column sums
and a compare tail today): every directed edge slot's neighbour id read
once (4 bytes), and a vertex's degree read and its coefficient written (8
bytes). The table's rows, the tiles' and the tail's traffic are the
implementation's, not the algorithm's, and are not in the count; nor are
the wedges, which are work and no bytes that any road must move. So the
share it gives bounds a claim and ranks nothing: a job that reads every
neighbour list once cannot take less."""


def count(shapes: dict) -> dict:
    slots, n = int(shapes["edge_slots"]), int(shapes["n"])
    return {"ops": slots + 2 * n, "bytes": 4 * slots + 8 * n}
