"""What one level of a BC job has to move, counted from the graph alone —
so it reads the same work whatever implements the level, and a level that
touched only its frontier's edges would read above this one's share:
every directed edge slot's neighbour id read and that neighbour's table
entry gathered (4 + 4 bytes), and a vertex's depth, path count and
dependency read and one of them written (16 bytes). A forward and a
backward level move the same: the table is sigma or (1 + delta) / sigma
masked to one level. One add an edge slot and a compare, a divide and a
multiply a vertex: bandwidth bounds it by three orders of magnitude."""


def count(shapes: dict) -> dict:
    slots, n = int(shapes["edge_slots"]), int(shapes["n"])
    return {"ops": slots + 4 * n, "bytes": 8 * slots + 16 * n}
