"""The refresh data set, its operation kind and its cell: the rehearsal
reports `correct` and the metrics the cell added; an answer that is the
state BEFORE the transaction it follows comes out as not correct; `load`'s
probe raises on a program whose extension declines; the bytes function
behind `tombstone_hbm_share` is arithmetic on span tags; and the control."""

import importlib.util
import json

import pytest
import run
from conftest import BENCH, ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = next(w["name"] for w in SPEC["workloads"]
            if w["traffic"] == "refresh-read")
NEW = ("write_ms_per_op", "delta_extend_ms_per_op", "rebuilds_per_op",
       "compact_ms_per_op", "delta_device_share", "tombstone_hbm_share")


def dataset():
    spec = importlib.util.spec_from_file_location(
        "bench_tests_tpch_refresh", BENCH / "datasets" / "tpch_refresh.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def rehearse(capsys, trace, seconds="4"):
    rc = run.main(["--workload", CELL, "--seed", "2147483659", "--seconds",
                   seconds, "--trace", str(trace), "--rehearsal-scale",
                   "0.05"])
    out = capsys.readouterr().out.strip().splitlines()
    return rc, [json.loads(ln) for ln in out]


def test_the_cell_as_declared():
    cell = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["chips"]) == ("tpch-refresh-sf4", 1)
    mix = json.loads((BENCH / "traffic" / "refresh-read.json").read_text())
    assert (mix["loop"], mix["clients"], mix["think_s"],
            mix["warmup_cycles"]) == ("closed", 1, 0, 2)
    assert mix["start_offset"] == {"seed": 0, "client": 0}
    assert mix["ops"] == [{"kind": "refresh_pair", "orders": 150,
                           "reads": ["Q1", "Q3", "Q6"]}]
    by = {m["name"]: m for m in SPEC["per_layer"]}
    assert all(by[n]["workloads"] == [CELL] for n in NEW)
    assert sum(CELL in m.get("workloads", ()) for m in SPEC["per_layer"]) \
        == 19 + len(NEW)
    cfg = json.loads((BENCH / "configs" / "tpch-refresh-sf4.json")
                     .read_text())
    assert {"atomicity", "freshness"} <= set(cfg["guarantees"])
    assert cfg["session"] == {"tidb_tpu_strict": "on"}


def test_the_rehearsal_is_correct_and_extends(capsys):
    rc, lines = rehearse(capsys, 0)
    last = lines[-1]
    assert rc == 0 and last["correct"] is True and last["failed"] == 0
    assert set(last["metrics"]) >= {"op_p50_ms", "ops_per_s", "setup_s"}
    window = next(ln for ln in lines if ln.get("phase") == "window")
    assert window["compile"]["requests"] == 0
    assert 0 < window["ledger"]["H2D_BYTES"] / window["attempted"] < 1 << 20


def test_the_traced_rehearsal_reports_the_new_metrics(capsys):
    rc, lines = rehearse(capsys, 1)
    last = lines[-1]
    assert rc == 0 and last["correct"] is True
    got = last["metrics"]
    # (the CPU's profile names no program on its modules line: the two
    # device shares find nothing to read here, and are left out)
    assert set(NEW) - {"delta_device_share", "tombstone_hbm_share"} \
        <= set(got)
    assert got["rebuilds_per_op"]["value"] == 0
    assert got["compiles_in_window"]["value"] == 0
    assert got["write_ms_per_op"]["value"] > 0
    assert got["delta_extend_ms_per_op"]["value"] > 0
    spans = next(ln for ln in lines if ln.get("phase") == "write_spans")
    assert {"write/write.stage", "write/write.commit", "delta/delta.diff",
            "delta/delta.tombstone", "delta/delta.upload"} \
        <= set(spans["self_ms_per_operation_by_name"])


def test_a_stale_answer_is_not_correct(capsys, monkeypatch):
    """The wire client answers one Q6 with the rows it gave the time
    before: the state BEFORE the transaction that the read follows."""
    from tidb_tpu.client import Client
    real = Client.query
    state = {"answers": 0, "last": None}

    def query(self, sql):
        names, rows = real(self, sql)
        if sql.lstrip().startswith("SELECT COUNT(*), SUM("):
            state["answers"] += 1
            if state["answers"] == 9 and state["last"] != rows:
                rows = state["last"]        # past warm-up, in the window
            else:
                state["last"] = rows
        return names, rows

    monkeypatch.setattr(Client, "query", query)
    rc, lines = rehearse(capsys, 0)
    last = lines[-1]
    assert state["answers"] >= 9
    assert rc == 0 and last["correct"] is False and last["failed"] == 1


def test_the_probe_raises_where_extension_declines(monkeypatch):
    from tidb_tpu.session import Engine
    from tidb_tpu.util import failpoint
    ds = dataset()
    eng = Engine()
    try:
        assert ds.require_extension(eng)["probe"][0]["extensions"] >= 1
        failpoint.enable("delta-merge-stale", value="test: stale diff")
        try:
            with pytest.raises(RuntimeError, match="did not extend"):
                ds.require_extension(eng)
        finally:
            failpoint.disable("delta-merge-stale")
        # ... and where a decline is counted
        from tidb_tpu.executor import delta
        monkeypatch.setattr(delta, "MIN_DELTA_CAP", 64)
        monkeypatch.setattr(delta, "DELTA_CAP_SHARE", 1 << 20)
        with pytest.raises(RuntimeError, match="delta-full"):
            ds.require_extension(eng)
    finally:
        eng.close()


def test_tombstone_bytes_are_arithmetic_on_span_tags():
    import tombstone_bytes
    spans = [{"args": {"rows": 8 << 20, "tombs": 211, "slab": 0}},
             {"args": {"rows": 1 << 20, "tombs": 3, "slab": 3}}]
    assert tombstone_bytes.moved_bytes(spans) == 2 * ((8 << 20) + (1 << 20))
    assert tombstone_bytes.window_bytes({"_span_events": []}) is None


@pytest.mark.parametrize("seed", [1, 2147483659])
def test_the_control_fails_q1_at_scale_1(seed):
    """`control.py` asks the data set for its float64 reference: it fails
    Q1 as `tpch_shaped`'s does (the cell's own scale: by hand,
    `python benchmarks/control.py --config tpch-refresh-sf4.json`)."""
    import control
    got = control.compare("tpch_refresh", 1, seed)
    assert got["wrong"] == ["Q1"], got
