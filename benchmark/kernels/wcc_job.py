"""What one WCC job has to move, counted from the graph alone — so it
reads the same work whatever implements it (a BFS peel and a label
propagation today): every directed edge slot's neighbour id read once (4
bytes), and a vertex's label read, its degree read and its label written
(12 bytes). One comparison an edge slot and two operations a vertex:
bandwidth bounds it by three orders of magnitude."""


def count(shapes: dict) -> dict:
    slots, n = int(shapes["edge_slots"]), int(shapes["n"])
    return {"ops": slots + 2 * n, "bytes": 4 * slots + 12 * n}
