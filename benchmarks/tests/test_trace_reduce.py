"""The trace reduction on synthetic intervals: overlaps, gaps, two device
lines, two chips, and the idle gaps by host lane."""

import pytest
import trace_reduce as t


def test_union_merges_overlaps_and_drops_empty():
    assert t.union([(3, 4), (0, 1), (0.5, 2), (2, 2)]) == [(0, 2), (3, 4)]
    assert t.total(t.union([(0, 1), (1, 2)])) == 2


def test_gaps_are_the_complement_inside_the_window():
    assert t.gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]
    assert t.gaps([(0, 5)], 1, 4) == []
    assert t.gaps([], 1, 4) == [(1, 4)]


def test_two_lines_of_one_chip_overlap_and_count_once():
    # line A busy 0-1 and 0.5-2 (overlap), line B busy 1.5-2.5 and 4-4.5
    got = t.busy_and_idle({"chip0": [[(0, 1), (0.5, 2)],
                                     [(1.5, 2.5), (4, 4.5)]]}, 0, 5)
    assert got["busy_s"] == pytest.approx(3.0)
    assert got["window_s"] == 5
    assert got["idle_share"] == pytest.approx(0.4)
    assert got["idle_gaps"] == [(2.5, 4), (4.5, 5)]


def test_busy_is_the_mean_over_chips_and_is_clipped_to_the_window():
    got = t.busy_and_idle({"a": [[(-1, 1)]], "b": [[(0, 4), (9, 12)]]}, 0, 10)
    assert got["busy_s"] == pytest.approx((1 + 5) / 2)
    assert got["idle_share"] == pytest.approx(0.7)
    # a gap is where no chip is busy
    assert got["idle_gaps"] == [(4, 9)]


def test_no_device_line_is_an_error_not_a_zero():
    with pytest.raises(ValueError):
        t.busy_and_idle({}, 0, 1)


def test_idle_gaps_go_to_the_host_lane_that_covers_them():
    idle = [(0, 1), (2, 4)]
    lanes = {"sched": [(0, 0.25), (3.5, 6)], "fetch": [(2, 3), (2.5, 3.5)]}
    got = dict(map(tuple, t.attribute_gaps(idle, lanes)))
    assert got["sched"] == pytest.approx(0.25 + 0.5)
    assert got["fetch"] == pytest.approx(1.5)
    assert got["(no lane)"] == pytest.approx(0.75)
    assert [n for n, _ in t.attribute_gaps(idle, lanes)][0] == "fetch"


def test_top_ops_sums_by_name_longest_first():
    ops = [("a", 1.0), ("b", 0.5), ("a", 0.25), ("c", 2.0)]
    assert t.top_ops(ops, top=2) == [["c", 2.0], ["a", 1.25]]


def test_reads_a_recorded_profile(tmp_path):
    """A real (CPU) profile through `read_xplane`: the sync annotation is
    found, XLA's host threads stand in for device lines."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(t.SYNC_NAME):
        pass
    for _ in range(3):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    got = t.read_xplane(t.find_xplane(str(tmp_path)), "cpu")
    assert got["sync_s"] is not None and got["chips"]
    lo = got["sync_s"]
    out = t.busy_and_idle(got["chips"], lo, lo + 1.0)
    assert 0 < out["busy_s"] < 1.0
    with pytest.raises(KeyError):
        t.read_xplane(t.find_xplane(str(tmp_path)), "abacus")
