"""The control of ``correct`` — the plain reference answering from a
stale epoch — comes out not correct, on three seeds, at a size a test run
can hold (scale 14; on the chip's machine it ran at the cells' own size,
PERF.md section 2)."""

import pytest

from conftest import small_config
import control
import files


@pytest.mark.parametrize("seed", [1, 2, 3000000019])
@pytest.mark.parametrize("graph,mix", [("kron", "hops2-open"),
                                       ("urand", "hops2-open-r5")])
def test_stale_epoch_control_is_not_correct(graph, mix, seed):
    out = control.control_run(small_config(graph, 14),
                              files.load_json("traffic", mix + ".json"),
                              seed)
    assert out["correct"] is False
    assert any(bad for bad, _of in out["compared"].values())


def test_the_reference_in_its_own_place_is_correct():
    out = control.control_run(small_config("kron", 14),
                              files.load_json("traffic", "hops2-open.json"),
                              5, stale_share=0.0)
    assert out["correct"] is True
