"""The control: the plain reference in the program's place.

Computed on the device in float32, it passes the comparison that decides
``correct``; in a lower precision it fails it. A CPU computes every
float32 matmul in full, whatever precision is asked, so the ``high``
control of the codec cells (three bf16 passes on a TPU) cannot be told
from float32 here: on the CPU the lower precision is bfloat16 throughout.
The chip readings of each cell's own control are in PERF.md.
"""

import pytest

from perfbench import control
from perfbench.tests.small import SMALL, execute, small_cell


def _run_with(impl: str, name: str) -> dict:
    saved = control.install(control.reference_engine(impl))
    try:
        return execute(small_cell(name))
    finally:
        control.restore(saved)


@pytest.mark.parametrize("name", sorted(SMALL))
def test_float32_reference_in_place_is_correct(name):
    res = _run_with("high", name)
    assert res["attempted"] > 0
    assert res["correct"] is True, res["checks"]


@pytest.mark.parametrize("name", sorted(SMALL))
def test_bfloat16_reference_in_place_is_not_correct(name):
    res = _run_with("bf16", name)
    assert res["attempted"] > 0
    assert res["correct"] is False, res["checks"]
