"""The control of the `correct` comparison: the reference put in the
program's place with every SUM accumulated in a float64 — the step below the
exact DECIMAL arithmetic the configurations guarantee — has to come out as
not correct, at the smallest scale a cell runs (SF=1), on three seeds. The
same comparison at the cells' own sizes is `benchmarks/control.py`."""

import pytest
import control

SEEDS = [11, 2147483659, 3000000019]


@pytest.mark.parametrize("seed", SEEDS)
def test_float64_sums_fail_text_equality_at_sf1(seed):
    got = control.compare("tpch_shaped", 1, seed)
    assert got["statements_wrong"] >= 1 > got["limit"]
    # it is Q1's charge sum, about 5e16 at scale 6, beyond 2**53
    assert "Q1" in got["wrong"]
