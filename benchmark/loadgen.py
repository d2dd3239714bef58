"""The load generator and the checker: a child process of ``run.py`` that
never imports JAX.

It makes the graph from the seed with the configuration's generator,
computes the plain reference while the parent builds the served graph,
then — told the server's address — sends the mix's traffic through the
mix's driver over loopback HTTP, holds every answer against the reference
and hands back the raw samples. Protocol: JSON lines on stdin (commands)
and stdout (events); everything else goes to stderr.

The general part of traffic generation lives here: pools drawn from the
seed, the request sequence, the arrival schedule, body templates, HTTP.
A driver only says how requests are paced and how an answer is awaited.
"""

from __future__ import annotations

import json
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np

import files

_POOL_TAG, _SEQ_TAG, _ARRIVAL_TAG = 0x706f6f6c, 0x736571, 0x617272


# -- what the seed decides ---------------------------------------------------

def make_graph(config: dict, seed: int):
    """(n, src, dst, perm) as served: the generator's edges, symmetrised
    when the configuration says the graph is undirected, and the seed's
    relabelling of the data set's vertices."""
    from reference import csr

    gen = files.load_module("graphs", config["generator"])
    n, src, dst, perm = gen.generate(config, seed)
    if config["undirected"]:
        src, dst = csr.symmetrise(src, dst)
    return n, src, dst, perm


def draw_pools(degree, mix: dict, config: dict, perm) -> dict:
    """Each pool of the mix: ``size`` distinct vertices of the DATA SET
    among those with an edge (GAP's source rule), drawn from the
    configuration's ``graph_seed`` (as GAP fixes its 64 sources per graph)
    and given under the ids this run's seed relabelled them to. So every
    seed asks about the same vertices of the same graph under other names,
    and the program meets the same shapes."""
    perm = np.asarray(perm)
    pools = {}
    for i, (name, spec) in enumerate(sorted(mix["pools"].items())):
        rng = np.random.default_rng(
            [int(config["graph_seed"]), _POOL_TAG, i])
        if spec["among"] != "nonzero_degree":
            raise ValueError(f"pool {name}: unknown 'among' "
                             f"{spec['among']!r}")
        among = np.flatnonzero(np.asarray(degree)[perm] > 0)
        pools[name] = [int(perm[v]) for v in rng.choice(
            among, int(spec["size"]), replace=False)]
    return pools


def request_order(pool_size: int, count: int, seed: int):
    """Pool indices of the first ``count`` requests: the whole pool over
    and over, each round in a new seeded order — every seed sends the
    same multiset of keys."""
    rng = np.random.default_rng([int(seed), _SEQ_TAG])
    out: list = []
    while len(out) < count:
        out.extend(int(i) for i in rng.permutation(pool_size))
    return out[:count]


def arrival_times(rate: float, seconds: float, seed: int):
    """Open-loop due times in [0, seconds): round(rate * seconds) arrivals
    whose gaps are the quantiles of the exponential distribution of that
    rate (a Poisson process's gaps), in a seeded order — every seed offers
    the same gaps, so the same load, in another order."""
    count = int(round(rate * seconds))
    q = (np.arange(count) + 0.5) / count
    gaps = -np.log1p(-q) / rate
    gaps *= seconds / gaps.sum()
    rng = np.random.default_rng([int(seed), _ARRIVAL_TAG])
    gaps = gaps[rng.permutation(count)]
    return np.cumsum(gaps) - gaps           # the last gap closes the window


def render(template, pick: dict):
    """A request body from the mix's template: ``{"draw": pool}`` is the
    pool entry this request drew."""
    if isinstance(template, dict):
        if set(template) == {"draw"}:
            return pick[template["draw"]]
        return {k: render(v, pick) for k, v in template.items()}
    if isinstance(template, list):
        return [render(v, pick) for v in template]
    return template


class Bodies:
    """The mix's request bodies in sending order, rendered on demand:
    ``get(i)`` is the i-th request's body whatever thread asks."""

    def __init__(self, mix: dict, pools: dict, seed: int):
        self.template = mix["request"]["body"]
        self.pools = pools
        self.seed = int(seed)
        self.drawn = sorted(_drawn_pools(self.template))
        self._orders = {name: [] for name in self.drawn}
        self._lock = threading.Lock()

    def _order(self, name: str, count: int) -> list:
        have = self._orders[name]
        if len(have) < count:
            want = max(count, 2 * len(have), 256)
            have[:] = request_order(
                len(self.pools[name]), want,
                self.seed + self.drawn.index(name))
        return have

    def get(self, i: int):
        with self._lock:
            pick = {name: self.pools[name][self._order(name, i + 1)[i]]
                    for name in self.drawn}
        return render(self.template, pick)


def _drawn_pools(template) -> set:
    if isinstance(template, dict):
        if set(template) == {"draw"}:
            return {template["draw"]}
        return set().union(*[_drawn_pools(v) for v in template.values()],
                           set())
    if isinstance(template, list):
        return set().union(*[_drawn_pools(v) for v in template], set())
    return set()


# -- HTTP --------------------------------------------------------------------

class RequestFailed(Exception):
    """The server refused, failed or timed out a request."""


class ConnectionFailed(RequestFailed):
    """The connection itself was refused or reset (the server's listen
    backlog overflowed): the request never reached the program."""


class Http:
    def __init__(self, base: str):
        self.base = base

    def call(self, path: str, payload=None, timeout: float = 600.0):
        req = urllib.request.Request(
            self.base + path,
            data=json.dumps(payload).encode() if payload is not None
            else None,
            headers={"Content-Type": "application/json"},
            method="POST" if payload is not None else "GET")
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                return json.loads(resp.read())
        except urllib.error.HTTPError as e:
            raise RequestFailed(
                f"HTTP {e.code}: "
                f"{e.read().decode(errors='replace')[:200]}") from e
        except (urllib.error.URLError, OSError) as e:
            cause = getattr(e, "reason", e)
            kind = ConnectionFailed if isinstance(cause, ConnectionError) \
                else RequestFailed
            raise kind(f"{type(cause).__name__}: {cause}") from e


def sample(i: int, due: float, sent: float, done: float, body: dict,
           envelope=None, result=None, why=None, reference=None) -> dict:
    """One request's record. ``ok`` = answered by the device path; the
    mismatch counts come from the reference."""
    s = {"i": i, "due": due, "sent": sent, "done": done,
         "latency_ms": (done - due) * 1e3, "ok": why is None,
         "why": why, "envelope": envelope or {}, "mismatch": {}}
    if why is None and reference is not None:
        s["mismatch"] = reference.check(body, result)
    return s


# -- the child process -------------------------------------------------------

def _emit(event: dict) -> None:
    sys.stdout.write(json.dumps(event) + "\n")
    sys.stdout.flush()


def log(msg: str) -> None:
    print(f"[loadgen] {msg}", file=sys.stderr, flush=True)


def main() -> int:
    from reference import csr

    job = json.loads(sys.stdin.readline())
    config, mix, seed = job["config"], job["mix"], int(job["seed"])
    t0 = time.time()
    n, src, dst, perm = make_graph(config, seed)
    t_gen = time.time() - t0
    indptr, indices = csr.structure(n, src, dst)
    degree = np.bincount(src, minlength=n)
    del src, dst
    pools = draw_pools(degree, mix, config, perm)
    reference = files.load_module("reference", mix["op"]).prepare(
        n, indptr, indices, pools, mix)
    driver = files.load_module("drivers", mix["driver"])
    log(f"graph {t_gen:.1f}s, reference ready after {time.time() - t0:.1f}s")
    _emit({"event": "ready", "graph_s": t_gen,
           "reference_s": time.time() - t0})
    for line in sys.stdin:
        cmd = json.loads(line)
        if cmd["cmd"] == "run":
            record = driver.run(Http(cmd["base"]), cmd.get("mix", mix),
                                pools, int(cmd.get("seed", seed)),
                                float(cmd["seconds"]), reference, _emit)
            _emit({"event": "done", **record})
        elif cmd["cmd"] == "exit":
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
