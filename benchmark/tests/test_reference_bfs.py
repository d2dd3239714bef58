"""The BFS reference against a queue-based BFS a vertex at a time and an
edge set in plain Python on 300 vertices, against shapes worked by hand,
and what ``check`` counts: GAP's rule for a parent array (the source its
own parent, a parent exactly where the serial BFS reaches, every other
reached vertex's parent a neighbour one level nearer), each way of
breaking it read by the count that names it, every valid tree read
clean."""

import numpy as np
import pytest

from reference import bfs, csr

CLEAN = dict.fromkeys(bfs.COMPARED, 0)


def both_ways(n, pairs):
    a = np.array([p[0] for p in pairs], np.int32)
    b = np.array([p[1] for p in pairs], np.int32)
    return csr.structure(n, *csr.symmetrise(a, b))


def queue_bfs(indptr, indices, s):
    """(depth list (None: unreached), for every reached vertex the list
    of ALL its valid parents) by the textbook queue."""
    n = len(indptr) - 1
    depth = [None] * n
    depth[s] = 0
    queue = [s]
    for u in queue:
        for v in indices[indptr[u]:indptr[u + 1]].tolist():
            if depth[v] is None:
                depth[v] = depth[u] + 1
                queue.append(v)
    valid = [[u for u in indices[indptr[v]:indptr[v + 1]].tolist()
              if depth[u] is not None and depth[v] is not None
              and depth[u] == depth[v] - 1] for v in range(n)]
    return depth, valid


def random_graph(seed: int, n: int = 300, m: int = 420):
    """Sparse enough for a dozen levels, several components and some
    vertices with no edge; a few hubs, so that rows differ in length."""
    rng = np.random.default_rng(seed)
    a = rng.integers(0, n, m)
    b = (n * rng.random(m) ** 3).astype(np.int64)
    keep = a != b
    return n, both_ways(n, list(zip(a[keep].tolist(), b[keep].tolist())))


@pytest.mark.parametrize("seed", range(6))
def test_depths_and_tree_against_the_queue(seed):
    n, (indptr, indices) = random_graph(seed)
    rng = np.random.default_rng(seed)
    for s in rng.choice(n, 8, replace=False).tolist():
        want, valid = queue_bfs(indptr, indices, s)
        depth = bfs.depths(indptr, indices, s)
        assert [None if d == bfs.UNREACHED else int(d) for d in depth] \
            == want
        parent = bfs.tree(indptr, indices, depth, s)
        assert parent[s] == s
        for v in range(n):
            if v == s:
                continue
            if want[v] is None:
                assert parent[v] == bfs.NO_PARENT
            else:
                assert int(parent[v]) in valid[v]
        assert bfs.broken(bfs.transposed(indptr, indices), depth, s,
                          parent) == CLEAN
        assert bfs.reached(depth) == sum(d is not None for d in want)
        assert bfs.levels(depth) == max(d for d in want
                                        if d is not None) + 1


@pytest.mark.parametrize("seed", range(4))
def test_every_valid_tree_reads_clean_and_every_other_does_not(seed):
    """A parent drawn at random among a vertex's valid ones is a tree
    GAP accepts; one drawn among its other neighbours, or among the
    vertices that are no neighbour, is out by the count that says so."""
    n, (indptr, indices) = random_graph(seed)
    rng = np.random.default_rng(100 + seed)
    s = int(np.argmax(np.diff(indptr)))
    want, valid = queue_bfs(indptr, indices, s)
    depth = bfs.depths(indptr, indices, s)
    into = bfs.transposed(indptr, indices)
    for _ in range(5):
        parent = np.full(n, bfs.NO_PARENT, np.int32)
        parent[s] = s
        for v in range(n):
            if v != s and want[v] is not None:
                parent[v] = int(rng.choice(valid[v]))
        assert bfs.broken(into, depth, s, parent) == CLEAN
    base = parent
    edges = set(zip(np.repeat(np.arange(n), np.diff(indptr)).tolist(),
                    indices.tolist()))
    reached = [v for v in range(n) if v != s and want[v] is not None]
    out = {"depth": 0, "edge": 0}
    bad = base.copy()
    for v in rng.choice(reached, 20, replace=False).tolist():
        row = indices[indptr[v]:indptr[v + 1]].tolist()
        others = [u for u in row if u not in valid[v]]
        if others and rng.random() < 0.5:
            bad[v] = int(rng.choice(others))    # a neighbour, wrong level
            out["depth"] += 1
        else:
            u = int(rng.choice([u for u in range(n)
                                if (u, v) not in edges and u != v]))
            bad[v] = u
            out["edge"] += 1
            out["depth"] += want[u] is None or want[u] != want[v] - 1
    assert bfs.broken(into, depth, s, bad) == {**CLEAN, **out}


def test_has_edge_is_the_edge_set():
    n, (indptr, indices) = random_graph(3, n=60, m=200)
    dense = np.zeros((n, n), bool)
    dense[np.repeat(np.arange(n), np.diff(indptr)), indices] = True
    u, v = np.divmod(np.arange(n * n), n)
    assert bfs.has_edge(bfs.transposed(indptr, indices), u, v).tolist() \
        == dense.ravel().tolist()
    # rows of length 0 and 1, the first and the last entry of a long one
    into = bfs.transposed(*both_ways(6, [(0, 1), (0, 2), (0, 3), (0, 4)]))
    u = np.array([0, 0, 0, 5, 1, 1, 4])
    v = np.array([1, 4, 5, 0, 0, 2, 0])
    assert bfs.has_edge(into, u, v).tolist() \
        == [True, True, False, False, True, False, True]
    assert bfs.has_edge(into, u[:0], v[:0]).tolist() == []


def test_on_a_directed_structure_the_edge_runs_from_the_parent():
    """One orientation only, 0 -> 1 -> 2 -> 3 and 0 -> 2: the tree
    follows the arrows (``transposed`` is what makes the edge test read
    the edges INTO a vertex, as GAP's verifier reads ``in_neigh``)."""
    indptr, indices = csr.structure(
        4, np.array([0, 1, 2, 0]), np.array([1, 2, 3, 2]))
    into = bfs.transposed(indptr, indices)
    assert bfs.has_edge(into, np.array([0, 1, 2, 0, 2, 3]),
                        np.array([1, 2, 3, 2, 0, 2])).tolist() \
        == [True, True, True, True, False, False]
    depth = bfs.depths(indptr, indices, 0)
    assert depth.tolist() == [0, 1, 1, 2]
    assert bfs.tree(indptr, indices, depth, 0).tolist() == [0, 0, 0, 2]
    assert bfs.broken(into, depth, 0, np.array([0, 0, 0, 2])) == CLEAN
    # 1 -> 2 is an edge, but 1 is no level nearer; 3 -> 2 is no edge
    assert bfs.broken(into, depth, 0, np.array([0, 0, 1, 2])) \
        == {**CLEAN, "depth": 1}
    assert bfs.broken(into, depth, 0, np.array([0, 2, 0, 2])) \
        == {**CLEAN, "depth": 1, "edge": 1}


def test_shapes_worked_by_hand():
    # a square with a tail, a pair apart, a vertex with no edge
    indptr, indices = both_ways(8, [(0, 1), (1, 2), (2, 3), (3, 0),
                                    (2, 4), (5, 6)])
    depth = bfs.depths(indptr, indices, 0)
    U = bfs.UNREACHED
    assert depth.tolist() == [0, 1, 2, 1, 3, U, U, U]
    # vertex 2 may hang under 1 or under 3: both are trees
    for under in (1, 3):
        parent = np.array([0, 0, under, 0, 2, -1, -1, -1], np.int32)
        assert bfs.broken(bfs.transposed(indptr, indices), depth, 0,
                          parent) == CLEAN
    # the pair apart: a tree of its own, nothing else reached
    depth = bfs.depths(indptr, indices, 6)
    assert bfs.tree(indptr, indices, depth, 6).tolist() \
        == [-1, -1, -1, -1, -1, 6, 6, -1]
    # the vertex with no edge is its own tree
    depth = bfs.depths(indptr, indices, 7)
    assert bfs.broken(bfs.transposed(indptr, indices), depth, 7,
                      np.array([-1] * 7 + [7])) == CLEAN


def test_what_check_counts_and_says(capsys):
    n, (indptr, indices) = random_graph(1)
    deg = np.diff(indptr)
    pool = [int(v) for v in np.flatnonzero(deg > 0)[:5]]
    reference = bfs.prepare(n, indptr, indices, {"source": pool}, {})
    assert sorted(reference.depth) == sorted(pool) and reference.n == n
    # a job's work: the slots out of what the median source reaches
    reach = sorted(int(deg[reference.depth[s] < bfs.UNREACHED].sum())
                   for s in pool)
    assert reference.edges == reach[len(reach) // 2]
    body = {"kind": "bfs", "source": pool[2], "parents": True}
    answer = reference.answer(body)
    assert reference.check(body, answer["result"]) == CLEAN
    said = capsys.readouterr().err
    assert f"[reference bfs] source {pool[2]}: " in said
    assert "reached " in said and "levels " in said and "check " in said
    # another length: every count reads all n
    assert reference.check(body, answer["result"][:-1]) \
        == dict.fromkeys(bfs.COMPARED, n)
    # depths in the parents' place are no tree
    assert sum(reference.check(body, answer["depth"]).values()) > 0
    # a source the pool does not hold is worked on demand
    other = next(v for v in range(n) if v not in pool and deg[v] > 0)
    assert reference.check({"source_dense": other},
                           reference.answer({"source": other})["result"]) \
        == CLEAN
    assert bfs.COMPARED == ("source", "reached", "depth", "edge")
