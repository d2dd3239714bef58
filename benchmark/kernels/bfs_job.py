"""What one BFS job has to move, counted from the graph and the answer
alone — so it reads the same work whatever implements a level (pushed
levels, a pulled level on a cap ladder and a stragglers' sweep today):
every directed edge slot out of the vertices the source reaches read once
(4 bytes: the neighbour's id; a level-synchronous search that examines
every edge out of every reached vertex, what the plain reference does),
and the answer written once, a depth and a parent a vertex (8 bytes, all
n of them: the arrays are n long whatever the source reaches).
``edge_slots`` is what the reference counted over its sources (the median
source's; at the cell's graph all but a few hundred vertices hang
together, so it is the graph's slots to four digits). A
direction-optimising search examines fewer edges than that (the pulled
level stops at a vertex's first parent) and moves other bytes beside them
(bitmaps, candidate lists, n-wide plans, a depth read before it is
written), which are the implementation's and not in the count. So the
share it gives bounds a claim and ranks nothing: it says how far a job's
device time stands from one pass over the image at the chip's bandwidth."""


def count(shapes: dict) -> dict:
    slots, n = int(shapes["edge_slots"]), int(shapes["n"])
    return {"ops": slots + 2 * n, "bytes": 4 * slots + 8 * n}
