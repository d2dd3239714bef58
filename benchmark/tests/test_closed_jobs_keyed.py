"""The keyed closed-loop driver against the stub of the wire that
``test_closed_jobs.py`` keeps: every job carries the body drawn for it,
two seeds send one multiset of keys, a mix that draws nothing renders as
``closed_jobs`` does and gives a record of the same shape, and its
warm-up sends jobs of distinct keys until one compiles nothing."""

import collections
import json
import time

import pytest

import files
import loadgen
from test_closed_jobs import MIX, N, Reference, Stub

POOLS = {"root_a": [3, 1, 4, 15], "root_b": [9, 2, 6, 5],
         "root_c": [35, 8, 97, 93]}
KEYED = dict(MIX, pools={name: {"size": 4, "among": "nonzero_degree"}
                         for name in POOLS})
KEYED["request"] = {"path": "/jobs", "body": {
    "kind": "pagerank", "iterations": 10, "timeout_s": 300,
    "sources": [{"draw": "root_a"}, {"draw": "root_b"},
                {"draw": "root_c"}]}}


@pytest.fixture
def driver():
    return files.load_module("drivers", "closed_jobs_keyed")


def run(driver, stub, seconds, mix, pools, seed=7):
    events, ref = [], Reference()
    try:
        record = driver.run(loadgen.Http(stub.base), mix, pools, seed,
                            seconds, ref, events.append)
    finally:
        stub.close()
    return record, events, ref


def test_every_job_carries_the_body_drawn_for_it(driver):
    stub = Stub(job_s=0.02)
    record, events, ref = run(driver, stub, 0.6, KEYED, POOLS)
    samples = record["samples"]
    assert len(samples) >= 12 and all(s["ok"] for s in samples)
    assert [s["i"] for s in samples] == list(range(len(samples)))
    bodies = loadgen.Bodies(KEYED, POOLS, 7)
    # what the server got, what the reference was handed, what the seed
    # draws: one multiset (two callers may swap neighbours)
    def tally(seq):
        return collections.Counter(json.dumps(b, sort_keys=True)
                                   for b in seq)
    drawn = tally(bodies.get(i) for i in range(len(samples)))
    got = [j["body"] for j in stub.jobs if j["job"] != "job-warm"]
    assert tally(got) == drawn == tally(b for b, _r in ref.seen)
    assert len({json.dumps(b["sources"]) for b in got}) > 4
    # each pool walked whole before any of it comes again
    for k, name in enumerate(sorted(POOLS)):
        for r in range(len(samples) // 4):
            seen = [bodies.get(i)["sources"][k]
                    for i in range(4 * r, 4 * r + 4)]
            assert sorted(seen) == sorted(POOLS[name])
    assert all(b["kind"] == "pagerank" and b["timeout_s"] == 300
               for b in got)


def test_two_seeds_send_one_multiset(driver):
    sent = {}
    for seed in (7, 3000000019):
        bodies = loadgen.Bodies(KEYED, POOLS, seed)
        sent[seed] = [tuple(bodies.get(i)["sources"]) for i in range(16)]
    assert sent[7] != sent[3000000019]
    for k in range(3):
        assert collections.Counter(s[k] for s in sent[7]) \
            == collections.Counter(s[k] for s in sent[3000000019])


def test_a_mix_that_draws_nothing_renders_as_closed_jobs_does(driver):
    plain = files.load_module("drivers", "closed_jobs")
    records = {}
    for name, mod in (("keyed", driver), ("plain", plain)):
        stub = Stub(job_s=0.05)
        events, ref = [], Reference()
        try:
            records[name] = mod.run(loadgen.Http(stub.base), MIX, {}, 7,
                                    0.4, ref, events.append)
        finally:
            stub.close()
        assert all(b == MIX["request"]["body"] for b, _r in ref.seen)
        assert [e["event"] for e in events] == ["window_start"]
        json.dumps(records[name])
    keyed, plain_ = records["keyed"], records["plain"]
    assert set(keyed) == set(plain_) == {"samples", "graph", "window"}
    assert keyed["graph"] == plain_["graph"] == {"n": N, "edge_slots": 640}
    assert set(keyed["window"]) == set(plain_["window"])
    assert abs(len(keyed["samples"]) - len(plain_["samples"])) <= 1
    for a, b in zip(keyed["samples"], plain_["samples"]):
        assert set(a) == set(b) and a["ok"] and b["ok"]
        assert set(a["envelope"]) == set(b["envelope"]) \
            == {"wait_ms", "exec_ms", "fetch_ms"}
        assert a["mismatch"] == b["mismatch"] == {"rank": 0}
        assert a["due"] == a["sent"]
    # the second caller half a job behind the first, as there
    first = sorted(s["sent"] for s in keyed["samples"])[:2]
    assert first[1] - first[0] == pytest.approx(0.025, abs=0.02)


def test_a_failed_job_is_a_failed_request(driver):
    stub = Stub(job_s=0.05, fail={1})       # the first of the window
    record, _events, ref = run(driver, stub, 0.3,
                               dict(KEYED, callers=1), POOLS)
    samples = record["samples"]
    assert [s["ok"] for s in samples] == [False] + [True] * (
        len(samples) - 1) and len(samples) >= 3
    assert "failed: stub: boom" in samples[0]["why"]
    assert samples[0]["mismatch"] == {} and samples[0]["envelope"] == {}
    assert len(ref.seen) == len(samples) - 1


def test_warm_sends_distinct_keys_until_one_compiles_nothing(driver):
    stub = Stub(job_s=0.01)
    counts = iter([0, 4, 4, 4])             # job 1 built four, job 2 none
    stub.compiles = lambda: next(counts)
    lines = []
    try:
        driver.warm(stub, KEYED, POOLS, lines.append)
    finally:
        stub.close()
    assert ["compiles=4" in ln for ln in lines] == [True, False]
    assert "float32[64]" in lines[0] and "result={'iterations': 10}" \
        in lines[0]
    sent = [j["body"]["sources"] for j in stub.jobs
            if j["job"] != "job-warm"]
    assert len(sent) == 2 and sent[0] != sent[1]
    assert not set(sent[0]) & set(sent[1])      # no key twice


def test_warm_fails_where_every_key_compiles(driver):
    stub = Stub(job_s=0.01)
    counts = iter(range(100))               # every job builds one
    stub.compiles = lambda: next(counts)
    lines = []
    try:
        with pytest.raises(RuntimeError, match="depend on the key"):
            driver.warm(stub, KEYED, POOLS, lines.append)
    finally:
        stub.close()
    assert len(lines) == 4                  # the pools' length, no more


def test_warm_fails_at_once_where_the_kind_is_refused(driver):
    """The parent commit has no ``bc`` row: ``POST /jobs`` answers 400
    and set-up raises after one request."""
    stub = Stub(job_s=0.01)
    lines = []
    real = loadgen.Http.call

    def call(self, path, payload=None, timeout=600.0):
        if payload is not None:
            raise loadgen.RequestFailed(
                "HTTP 400: unknown job kind 'bc' (known: bfs, ...)")
        return real(self, path, payload, timeout)
    t0 = time.time()
    try:
        loadgen.Http.call = call
        with pytest.raises(RuntimeError, match="warm-up: HTTP 400"):
            driver.warm(stub, KEYED, POOLS, lines.append)
    finally:
        loadgen.Http.call = real
        stub.close()
    assert time.time() - t0 < 1.0 and lines == []
    assert [j["job"] for j in stub.jobs] == ["job-warm"]
