"""What one PageRank iteration has to move, counted from the graph alone
— so it reads the same work whatever kernel implements it: every directed
edge slot's neighbour id read and its contribution gathered (4 + 4 bytes),
and a vertex's rank read, its degree read and its new rank written (12
bytes). One multiply-add an edge slot and two a vertex: bandwidth bounds
it by three orders of magnitude."""


def count(shapes: dict) -> dict:
    slots, n = int(shapes["edge_slots"]), int(shapes["n"])
    return {"ops": 2 * slots + 4 * n, "bytes": 8 * slots + 12 * n}
