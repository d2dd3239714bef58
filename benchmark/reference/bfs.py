"""Plain reference for BFS jobs: the GAP Benchmark Suite's BFS kernel
(Beamer, Asanovic, Patterson, arXiv:1508.03619, section 3.1 and
``bfs.cc``; Graph500's kernel 2 is the same search) over the structure
``reference/csr.py`` makes of the generated edges, in numpy (and
scipy's sparse rows for the edge test), nothing of ``titan_tpu`` in it.
The kernel's answer is the PARENT ARRAY of a BFS tree from the source,
and a tree is not unique, so the suite's verifier (``BFSVerifier``)
holds an answer to a rule and not to one array. The rule, for a source
s and the depths of a serial BFS from s:

    parent[s] = s
    parent[v] >= 0  exactly where the serial BFS reaches v
    for every other reached v:  depth[parent[v]] = depth[v] - 1
                                (parent[v], v) is an edge of the graph

``prepare`` works the depths of every source of every pool ahead of the
window on a thread pool, level by level: the frontier's rows' neighbours
listed with their repeats (``np.repeat`` of the rows' starts, one
``indices`` read an edge), those without a depth stamped ``level + 1``
and made the next frontier, until a frontier is empty. No direction rule,
no bitmap, no cap: every edge out of every reached vertex is read once.
It keeps each depth array (int32 [n]: 64 sources are 0.6 GB at the cell's
size). ``check`` holds the fetched ``parent`` (int32 [n], dense ids, -1
where the source reaches nobody) to the rule for the source THAT job
named, all n vertices a job, and returns four counts of vertices that
break it, each with limit 0 (``COMPARED``):

    source    parent[s] != s (0 or 1)
    reached   parent[v] >= 0 where the serial BFS does not reach v, or
              < 0 where it does
    depth     a reached v other than s whose parent is no vertex, or
              whose parent's depth is not depth[v] - 1
    edge      a reached v other than s with no edge (parent[v], v): as
              GAP's verifier looks for parent[v] among in_neigh(v), a
              look along the row of the edges INTO v, all n a job

An answer of another length counts as all n out in each. Ids are
integers, so there is no precision to fall short of: one altered parent
reads at least 1 in one of the counts (``tests/test_served_bfs.py``).
The check writes what it read and the milliseconds it took to stderr: it
runs on the caller's thread between two jobs, and has to take less than
a job does or two callers no longer keep the worker back to back.
``reached`` and ``levels`` are the envelope's two integers (the vertices
with a depth; the levels that hold a vertex), and ``edges`` the directed
edge slots out of the vertices a source reaches, median over the pools'
sources: what ``kernels/bfs_job.py`` counts a job's work from.
"""

from __future__ import annotations

import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from reference import csr

COMPARED = ("source", "reached", "depth", "edge")

#: the depth of a vertex the source does not reach (what the served kind
#: stamps in ``dist``: the configuration's ``algorithm.unreached``)
UNREACHED = 1 << 30
#: the parent of a vertex the source does not reach
NO_PARENT = -1
WORKERS = 4
TILE = 1 << 18


def depths(indptr, indices, source: int) -> np.ndarray:
    """int32 [n]: every vertex's BFS depth from ``source``, UNREACHED
    where there is none. A frontier is read a tile of at most ``TILE``
    edges at a time, so a level of a hundred million edges holds no array
    of that length: a tile's temporaries are a few megabytes, which the
    allocator hands round again and the caches hold (four threads on
    tiles of 2^18 work the cell's 64 sources as fast as eight did on
    2^22, at a third of the memory)."""
    n = len(indptr) - 1
    depth = np.full(n, UNREACHED, np.int32)
    depth[source] = 0
    frontier = np.asarray([source], np.int64)
    level = 0
    while len(frontier):
        new = np.zeros(n, bool)
        mass = np.cumsum(indptr[frontier + 1] - indptr[frontier])
        cuts = np.searchsorted(mass, np.arange(TILE, int(mass[-1]), TILE))
        for tile in np.split(frontier, np.unique(cuts)):
            got = csr.neighbours(indptr, indices, tile)
            new[got[depth[got] == UNREACHED]] = True
        frontier = np.flatnonzero(new)
        depth[frontier] = level + 1
        level += 1
    return depth


def reached(depth) -> int:
    return int((np.asarray(depth) < UNREACHED).sum())


def levels(depth) -> int:
    """The levels that hold a vertex: the deepest depth and one."""
    depth = np.asarray(depth)
    return int(depth[depth < UNREACHED].max()) + 1


def tree(indptr, indices, depth, source: int) -> np.ndarray:
    """int32 [n]: ONE valid parent array for ``depth`` (a serial BFS's
    from ``source``): every reached vertex but the source is given a
    neighbour one level nearer (of several, the one in the last row that
    names it), the source itself, the rest NO_PARENT. What the reference
    would answer in the program's place (``control.py`` puts it there
    with a stale epoch; the window's check needs no tree). A tile of
    rows at a time, as ``depths`` reads a frontier."""
    n = len(depth)
    parent = np.full(n, NO_PARENT, np.int32)
    rows = np.searchsorted(indptr, np.arange(TILE, int(indptr[-1]), TILE),
                           side="right") - 1
    for lo, hi in zip(np.r_[0, rows], np.r_[rows, n]):
        u = np.repeat(np.arange(lo, hi), np.diff(indptr[lo:hi + 1]))
        v = indices[indptr[lo]:indptr[hi]]
        ok = (depth[u] < UNREACHED) & (depth[v] == depth[u] + 1)
        parent[v[ok]] = u[ok]
    parent[source] = source
    return parent


def transposed(indptr, indices):
    """The edges INTO every vertex, as a scipy CSR matrix: ``into[v, u]``
    is 1 where u -> v is an edge. What ``has_edge`` is asked from a
    vertex's side, as GAP's verifier looks for a parent among
    ``in_neigh(v)``. An undirected data set's is its structure again; it
    is made all the same, once a run, so that nothing here has to be told
    which kind it reads."""
    import scipy.sparse as sp

    n = len(indptr) - 1
    into = sp.csr_matrix((np.ones(len(indices), np.int8), indices, indptr),
                         shape=(n, n)).T.tocsr()
    into.sort_indices()
    return into


def has_edge(into, u, v) -> np.ndarray:
    """bool, one an entry: whether ``u[i] -> v[i]`` is an edge: scipy's
    sampling of ``into`` at ``(v, u)``, a walk of v's row in C (a
    vertex's row is 53 long in the mean at the cell's graph; a bisection
    of every row at once in numpy took three times as long)."""
    if not len(u):
        return np.zeros(0, bool)
    return np.asarray(into[v, u]).ravel() != 0


def broken(into, depth, source: int, parent) -> dict:
    """The four counts of ``COMPARED`` of one answer against the serial
    BFS's ``depth`` from ``source``; ``into`` the edges INTO every vertex
    (``transposed``)."""
    n = len(depth)
    parent = np.asarray(parent).ravel()
    if parent.shape != (n,):
        return dict.fromkeys(COMPARED, n)
    parent = parent.astype(np.int64)
    there = depth < UNREACHED
    out = {"source": int(parent[source] != source),
           "reached": int(((parent >= 0) != there).sum())}
    rest = there.copy()
    rest[source] = False
    v = np.flatnonzero(rest)
    p = parent[v]
    named = (p >= 0) & (p < n)
    q = np.where(named, p, 0)
    out["depth"] = int((~named | (depth[q] != depth[v] - 1)).sum())
    out["edge"] = int((~named | ~has_edge(into, q, v)).sum())
    return out


def source_of(body: dict) -> int:
    return int(body["source_dense"] if "source_dense" in body
               else body["source"])


class Reference:
    def __init__(self, n, indptr, indices, pools: dict, mix: dict):
        self.n = int(n)
        self.indptr, self.indices = indptr, indices
        self.into = transposed(indptr, indices)
        self.depth: dict = {}
        sources = sorted({int(s) for pool in pools.values() for s in pool})
        with ThreadPoolExecutor(WORKERS) as workers:
            for source, depth in zip(sources, workers.map(
                    lambda s: depths(indptr, indices, s), sources)):
                self.depth[source] = depth
        # the directed edge slots out of what a source reaches (median
        # over the sources: a job's work, kernels/bfs_job.py); the whole
        # graph's where no pool names a source
        degree = np.diff(indptr)
        slots = sorted(int(degree[d < UNREACHED].sum())
                       for d in self.depth.values())
        self.edges = slots[len(slots) // 2] if slots else int(len(indices))

    def depth_of(self, source: int) -> np.ndarray:
        if source not in self.depth:        # a source no pool holds
            self.depth[source] = depths(self.indptr, self.indices, source)
        return self.depth[source]

    def answer(self, body: dict) -> dict:
        """A valid parent array for the body's source (``tree``: the
        rule has no one right array to hand out), and the serial BFS's
        depths it was made from."""
        source = source_of(body)
        depth = self.depth_of(source)
        return {"result": tree(self.indptr, self.indices, depth, source),
                "depth": depth}

    def check(self, body: dict, result) -> dict:
        t0 = time.time()
        want = self.depth_of(source_of(body))
        out = broken(self.into, want, source_of(body), result)
        print(f"[reference bfs] source {source_of(body)}: {out} of "
              f"{self.n} vertices, reached {reached(want)}, levels "
              f"{levels(want)}, check {(time.time() - t0) * 1e3:.0f}ms",
              file=sys.stderr, flush=True)
        return out


def prepare(n, indptr, indices, pools, mix) -> Reference:
    return Reference(n, indptr, indices, pools, mix)
