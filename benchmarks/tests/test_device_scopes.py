"""The reduction by stage and program (`device_scopes.py`) and the span
reduction (`span_reduce.py`) on a recorded (CPU) profile and on synthetic
events; the arithmetic itself is also in tier-1 (tests/test_span_reduce.py)."""

import device_scopes as d
import pytest
import span_reduce
import trace_reduce as t


def test_scope_and_program_from_an_op_name():
    op = "jit(partial_fused_ab12cd34)/jit(main)/finalize/merge/reduce_sum"
    assert d.scope_of(op) == "merge"
    assert d.program_of(op) == "partial_fused_ab12cd34"
    assert d.scope_of("jit(x)/jit(main)/mul") == d.UNSCOPED
    assert d.program_of("", "jit_merge_0a0a0a0a(77)") == "merge_0a0a0a0a"


def test_operands_come_before_what_an_instruction_calls():
    text = ("%reduce-window.1 = (u32[8,128]{0,1}, u32[8,128]{0,1}) "
            "reduce-window(u32[8,128]{0,1} %shift-right-logical_and_fusion, "
            "u32[] %constant.65), window={size=1x128}, "
            "to_apply=%region_0.1.clone")
    assert d.operands(text) == ["shift-right-logical_and_fusion",
                                "constant.65", "region_0.1.clone"]
    assert d.operands("ThunkExecutor::Execute") == []


def test_nested_device_operations_are_counted_once():
    line = [(0.0, 10.0, "while"), (1.0, 4.0, "a"), (5.0, 9.0, "b"),
            (10.0, 12.0, "f")]
    got = dict(d.self_seconds(line))
    assert got["while"] == pytest.approx(3.0)
    assert sum(got.values()) == pytest.approx(12.0)


def test_reads_a_recorded_profile_by_program(tmp_path):
    """The synthetic profile of test_trace_reduce.py, with a named program
    and a host annotation: XLA's CPU thunks carry the module's name and no
    op_name, so every second is the program's and unscoped."""
    import functools

    import jax
    import jax.numpy as jnp

    def body(x):
        with jax.named_scope("agg"):
            return (x @ x).sum()

    @functools.wraps(body)
    def program(x):
        return body(x)
    program.__name__ = program.__qualname__ = "partial_chain_0123abcd"
    f = jax.jit(program)
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("tidb_tpu/stmt/stmt", req=1, conn=2):
        for _ in range(3):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    got = d.read(t.find_xplane(str(tmp_path)), "cpu")
    assert got["busy_s"] > 0
    assert got["annotations"] == 1
    assert "partial_chain_0123abcd" in got["by_program"]
    assert got["op_name_stat"] is None and not got["inherited_s"]
    assert set(got["by_scope"]) == {d.UNSCOPED}
    assert sum(got["by_scope"].values()) == pytest.approx(got["busy_s"])
    with pytest.raises(KeyError):
        d.read(t.find_xplane(str(tmp_path)), "abacus")


def test_share_reads_nothing_from_an_untraced_run():
    assert d.share({"trace": None}, ("decode",)) is None


def test_span_self_times_on_a_nest():
    def ev(cat, ts, dur, id_, parent=0):
        return {"name": cat, "cat": cat, "ph": "X", "ts": ts, "dur": dur,
                "pid": 1, "tid": 1,
                "args": {"req": 1, "id": id_, "parent": parent}}
    nest = [ev("stmt", 0, 100, 1), ev("frag", 10, 80, 2, 1),
            ev("launch", 20, 10, 3, 2), ev("drain", 30, 50, 4, 2)]
    got = span_reduce.reduce(nest)
    assert got["ops"] == 1 and got["launches"] == 1
    assert got["self_s"]["stmt"] == pytest.approx(20e-6)
    assert got["self_s"]["frag"] == pytest.approx(20e-6)
    assert got["self_s"]["drain"] == pytest.approx(50e-6)
