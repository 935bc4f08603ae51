"""The control, kept runnable at a size a test holds: the reference put
in the program's place one precision step below the configuration
comes out as not correct under the harness's own verdict and the
cell's own limits, while the program on the same run comes out
correct."""

import pytest

from onchip_testlib import SEED, tiny_checkout

_READINGS = {}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny_checkout(tmp_path_factory.mktemp("onchip-control"))


def _readings(checkout, cell):
    if cell not in _READINGS:
        import control
        from vbench.registry import Cell
        c = Cell(cell, base=checkout / "benchmarks" / "onchip")
        _READINGS[cell] = control.readings(c, [SEED], 2.0,
                                           emit=lambda s: None)
    return _READINGS[cell]


@pytest.mark.parametrize("cell", ["bgevl-base.query", "bgevl-large.ingest"])
def test_control_is_not_correct_and_the_program_is(checkout, cell):
    _, correct = _readings(checkout, cell)
    assert correct["program"] == [True]
    assert correct["control"] == [False]


@pytest.mark.parametrize("cell,number", [
    ("bgevl-base.query", "topk_gap"),
    ("bgevl-base.query", "draw_gap"),
    ("bgevl-base.query", "index_mismatch"),
    ("bgevl-large.ingest", "image_emb_gap"),
])
def test_control_reads_apart_from_the_program(checkout, cell, number):
    got, _ = _readings(checkout, cell)
    assert got[number]["program_max"] <= got[number]["limit"]
    assert got[number]["control_min"] > got[number]["limit"]
    assert got[number]["control_min"] > 3 * got[number]["program_max"]
