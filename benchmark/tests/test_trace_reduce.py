"""The reduction from a trace to numbers: on made-up planes with known
answers, and on a small trace recorded on the chip."""

import os

import pytest

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
SMALL = os.path.join(HERE, "data", "small.xplane.pb")


def ev(start_us, dur_us, name):
    return (start_us * 1e3, (start_us + dur_us) * 1e3, name)


def test_union_self_time_and_gaps():
    ops = [ev(0, 100, "while"), ev(10, 30, "gather"), ev(50, 40, "scatter"),
           ev(200, 50, "gather"), ev(240, 30, "sum"),      # overlaps
           ev(1000, 10, "copy")]
    host = [ev(100, 95, "readback"), ev(270, 700, "sleep"),
            ev(960, 45, "dispatch")]
    planes = [("/device:TPU:0", [("XLA Ops", sorted(ops)),
                                 ("Steps", [ev(0, 2000, "step")])]),
              ("/host:CPU", [("thread-1", host)])]
    out = trace_reduce.reduce_planes(planes, window_s=0.002)
    assert out["devices"] == 1
    assert out["busy_s"] == pytest.approx((100 + 70 + 10) * 1e-6)
    ops_s = dict(out["device_ops"])
    assert ops_s["while"] == pytest.approx(30e-6)       # 100 - 30 - 40
    assert ops_s["gather"] == pytest.approx(70e-6)      # 30 + (50 - 10)
    assert ops_s["sum"] == pytest.approx(30e-6)
    assert sum(ops_s.values()) == pytest.approx(out["busy_s"])
    assert out["idle_gaps"] == [
        ["host:sleep", pytest.approx(730e-6)],
        ["host:readback", pytest.approx(100e-6)]]


def test_hlo_text_is_cut_to_name_and_shape():
    assert trace_reduce.short_name(
        "%fusion.50 = u8[8388608]{0:T(1024)(128)(4,1)S(1)} fusion(u8[13]{0} "
        "%reduce.17), kind=kCustom") == "fusion.50 u8[8388608]"
    assert trace_reduce.short_name(
        "%copy-start = (bf16[2048,2048]{1,0}, u32[]{:S(2)}) copy-start("
        "bf16[2048,2048]{1,0} %a.1)") == "copy-start bf16[2048,2048]"
    assert trace_reduce.short_name("ReadSyncFlag") == "ReadSyncFlag"


def test_busy_is_averaged_over_device_planes():
    planes = [(f"/device:TPU:{i}", [("XLA Ops", [ev(0, 100 * (i + 1), "op")])])
              for i in range(2)]
    out = trace_reduce.reduce_planes(planes, window_s=1.0)
    assert out["devices"] == 2 and out["busy_s"] == pytest.approx(150e-6)


@pytest.mark.skipif(not os.path.isfile(SMALL),
                    reason="no recorded chip trace in tests/data")
def test_a_small_trace_recorded_on_the_chip():
    """record_trace.py: 3 bursts of 4 jitted matrix products, 50 ms of
    host sleep after each — so 12 fusions, two long gaps between bursts."""
    text = trace_reduce.describe(SMALL)
    assert "/device:TPU:0" in text and "XLA Ops" in text
    out = trace_reduce.reduce_file(SMALL, window_s=0.2)
    assert out["devices"] == 1
    assert 0 < out["busy_s"] < 0.1
    assert out["device_ops"] and out["device_ops"][0][1] > 0
    gaps = [g for _name, g in out["idle_gaps"]]
    assert len(gaps) >= 2 and gaps[0] >= gaps[1] >= 0.04
