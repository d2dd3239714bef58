"""How ``trace_reduce`` names an idle gap once the program annotates its
phases: a leaf annotation round a readback names the gap inside it, and
an annotation that enclosed the whole batch would take every gap — which
is why the program annotates leaves only, and only on the thread that
drives the device."""

import trace_reduce


def ev(start_us, dur_us, name):
    return (start_us * 1e3, (start_us + dur_us) * 1e3, name)


# three device ops with two gaps: [100, 300) and [400, 1000)
OPS = [ev(0, 100, "fusion.1"), ev(300, 100, "fusion.2"),
       ev(1000, 50, "fusion.3")]
# the lane's thread: the first gap falls inside a plan's readback, the
# second inside the next sweep's
LEAVES = [ev(0, 310, "bfs.plan L1"), ev(90, 215, "np.asarray(jax.Array)"),
          ev(320, 700, "bfs.sweep L1"),
          ev(395, 620, "np.asarray(jax.Array)")]
# an HTTP thread waiting for its answer all along
WAITER = [ev(0, 1100, "Event.wait")]


def gaps(host_lines):
    planes = [("/device:TPU:0", [("XLA Ops", OPS)]),
              ("/host:CPU", host_lines)]
    return trace_reduce.reduce_planes(planes, 0.0011)["idle_gaps"]


def test_a_leaf_annotation_names_its_gap():
    got = gaps([("serving-interactive", LEAVES)])
    assert [n for n, _s in got] == ["host:bfs.sweep L1", "host:bfs.plan L1"]
    assert [round(s * 1e6) for _n, s in got] == [600, 200]


def test_an_enclosing_annotation_would_take_every_gap():
    nested = [ev(0, 1100, "lane.batch")] + LEAVES
    got = gaps([("serving-interactive", nested)])
    assert {n for n, _s in got} == {"host:lane.batch"}


def test_so_would_a_wait_on_a_thread_listed_first():
    # ties go to the event met first, and lines come in the trace's
    # order: nothing on the HTTP threads is annotated, and the Python
    # tracer that would record their waits is off in a traced run
    got = gaps([("http-1", WAITER), ("serving-interactive", LEAVES)])
    assert {n for n, _s in got} == {"host:Event.wait"}
    got = gaps([("serving-interactive", LEAVES), ("http-1", WAITER)])
    assert [n for n, _s in got] == ["host:bfs.sweep L1", "host:bfs.plan L1"]


def test_a_gap_between_two_phases_goes_to_the_longer_overlap():
    # [400, 1000): 150 us under the tail of a plan, 450 under the sweep
    host = [ev(0, 550, "bfs.plan L2"), ev(550, 500, "bfs.sweep L2")]
    got = dict(gaps([("serving-interactive", host)]))
    assert set(got) == {"host:bfs.sweep L2", "host:bfs.plan L2"}
    assert round(got["host:bfs.sweep L2"] * 1e6) == 600
