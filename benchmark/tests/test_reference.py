"""Each plain reference on a 2^8 graph against a brute-force loop."""

import pytest

from conftest import small_config
import files
from reference import csr, hops


def graph(name: str, seed: int):
    cfg = dict(small_config(name, 10), scale=8)
    gen = files.load_module("graphs", cfg["generator"])
    n, src, dst, _perm = gen.generate(cfg, seed)
    src, dst = csr.symmetrise(src, dst)
    adj = [set() for _ in range(n)]
    for s, d in zip(src.tolist(), dst.tolist()):
        adj[s].add(d)
    return n, csr.structure(n, src, dst), adj


@pytest.mark.parametrize("name", ["kron", "urand"])
@pytest.mark.parametrize("seed", [5, 3000000019])
def test_hop_counts_are_walks(name, seed):
    n, (indptr, indices), adj = graph(name, seed)
    for start in range(0, n, 17):
        cur = {start}
        for _ in range(2):
            cur = set().union(*[adj[u] for u in cur]) if cur else set()
        assert hops.hop_count(indptr, indices, start, 2) == len(cur)


def test_hops_check_counts_a_mismatch():
    n, (indptr, indices), _adj = graph("kron", 9)
    starts = list(range(0, n, 31))
    ref = hops.prepare(n, indptr, indices, {"start": starts},
                       {"request": {"body": {"hops": 2}}})
    body = {"start": [starts[3]]}
    good = ref.answer(body)["result"]
    assert good == hops.hop_count(indptr, indices, starts[3], 2)
    assert ref.check(body, good) == {"hop_count": 0}
    assert ref.check(body, good + 1) == {"hop_count": 1}
    assert ref.check(body, None) == {"hop_count": 1}
