"""The throughput data set, its operation kind and its cell: the cell as
declared; the kind's `check` refuses an answer of a state below lo, above
hi, of a mix of two states, and a state that goes backwards on one
connection; the rehearsal is `correct` and its traced form reports the
metrics the cell added; `load`'s older-snapshot probe raises on a program
whose cache answers a read behind it by a rebuild."""

import importlib.util
import json

import pytest
import run
from conftest import BENCH, ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = next(w["name"] for w in SPEC["workloads"]
            if w["traffic"] == "throughput-s3")
NEW = ("kept_generation_read_share", "stale_rebuilds_per_op",
       "generations_kept_bytes", "stream_stmt_ms", "refresh_txn_ms",
       "commit_wait_ms_per_txn", "refresh_share")
K = 15


def load(kind: str, name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_tests_{kind}_{name}", BENCH / kind / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def rehearse(capsys, trace, seconds="6"):
    rc = run.main(["--workload", CELL, "--seed", "2147483659", "--seconds",
                   seconds, "--trace", str(trace), "--rehearsal-scale",
                   "0.05"])
    out = capsys.readouterr().out.strip().splitlines()
    return rc, [json.loads(ln) for ln in out]


def test_the_cell_as_declared():
    cell = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["chips"]) == ("tpch-throughput-sf4", 1)
    mix = json.loads((BENCH / "traffic" / "throughput-s3.json").read_text())
    assert (mix["loop"], mix["clients"], mix["think_s"],
            mix["warmup_cycles"]) == ("closed", 4, 0, 12)
    assert mix["ops"] == [{"kind": "throughput_streams", "streams": 3,
                           "orders": 150, "reads": ["Q1", "Q3", "Q6"]}]
    by = {m["name"]: m for m in SPEC["per_layer"]}
    assert all(by[n]["workloads"] == [CELL] for n in NEW)
    assert sum(CELL in m.get("workloads", ()) for m in SPEC["per_layer"]) \
        == 23 + 6 + len(NEW)
    cfg = json.loads((BENCH / "configs" / "tpch-throughput-sf4.json")
                     .read_text())
    refresh = json.loads((BENCH / "configs" / "tpch-refresh-sf4.json")
                         .read_text())
    assert cfg["rows"] == refresh["rows"] and cfg["scale"] == 4
    assert {"isolation", "atomicity", "arithmetic", "device_path",
            "durability"} <= set(cfg["guarantees"])
    assert {"generations_kept", "generations_kept_bytes",
            "no_compaction_in_window", "clause_numbers"} \
        <= set(cfg["assumed"])
    assert cfg["session"] == {"tidb_tpu_strict": "on"}


def test_the_reference_imports_nothing_of_the_program():
    for name in ("tpch_throughput", "tpch_refresh"):
        text = (BENCH / "datasets" / f"{name}.py").read_text()
        head = text[:text.index("# load, behind the probe")]
        assert "tidb_tpu" not in head.replace("tidb_tpu_", ""), name


def test_the_comparison_refuses_what_isolation_forbids():
    ds = load("datasets", "tpch_throughput")
    kind = load("ops", "throughput_streams")
    data = ds.generate(0.01, 2147483659)
    op = kind.bind({"kind": "throughput_streams", "streams": 1, "orders": K,
                    "reads": ["Q1", "Q3", "Q6"]}, ds, None)
    ref = ds.reference(data)
    state = ref[ds.STATE] = ds.RefreshState(data, 2147483659, k=K)

    def judged(reads) -> list:
        op["history"] = {0: [
            {"role": "stream", "conn": 0, "seq": i, "reads": [
                {"q": q, "rows": rows, "lo": lo, "hi": hi}]}
            for i, (q, rows, lo, hi) in enumerate(reads)]}
        op["verdicts"] = None
        return [kind.check(op, a, ref) for a in op["history"][0]]

    at = lambda s, q: ds.at(state, s)[q]  # noqa: E731
    assert judged([("Q3", at(3, "Q3"), 2, 4)]) == [True]
    assert judged([("Q3", at(1, "Q3"), 2, 4)]) == [False]      # below lo
    assert judged([("Q3", at(5, "Q3"), 2, 4)]) == [False]      # above hi
    mixed = list(at(3, "Q3")[:2]) + list(at(2, "Q3")[2:])
    assert judged([("Q3", mixed, 2, 4)]) == [False]            # two states
    assert judged([("Q1", at(4, "Q1"), 2, 4),
                   ("Q1", at(3, "Q1"), 2, 4)]) == [True, False]  # backwards
    assert judged([("Q1", at(3, "Q1"), 2, 4),
                   ("Q1", at(4, "Q1"), 2, 4)]) == [True, True]
    # state s as the reference numbers it: 0 as loaded, 2n+1 after RF1 n
    assert at(0, "Q6") == state.base["Q6"]
    assert at(1, "Q6") == state.after(0, "rf1")["Q6"]
    assert at(4, "Q6") == state.after(1, "rf2")["Q6"]
    assert [ds.which(t) for t in range(4)] == [
        (0, "rf1"), (0, "rf2"), (1, "rf1"), (1, "rf2")]


def test_the_rehearsal_is_correct(capsys):
    rc, lines = rehearse(capsys, 0)
    last = lines[-1]
    assert rc == 0 and last["correct"] is True and last["failed"] == 0
    assert set(last["metrics"]) >= {"op_p50_ms", "op_p95_ms", "ops_per_s",
                                    "setup_s"}
    compared = next(ln for ln in lines if ln.get("phase") == "compared")
    assert compared["operations_wrong"] == 0
    assert compared["setup_operations_compared"] == 1 + 4 * 12
    window = next(ln for ln in lines if ln.get("phase") == "window")
    assert window["ops_by_name"] == {"throughput_streams": last["attempted"]}


def test_the_traced_rehearsal_reports_the_new_metrics(capsys):
    rc, lines = rehearse(capsys, 1)
    last = lines[-1]
    assert rc == 0 and last["correct"] is True
    assert set(NEW) <= set(last["metrics"]), sorted(last["metrics"])
    roles = next(ln for ln in lines if ln.get("phase") == "roles")
    assert roles["stream"]["operations"] and roles["refresh"]["operations"]
    spans = next(ln for ln in lines if ln.get("phase") == "stream_spans")
    assert spans["statements"] and spans["txns"]
    share = last["metrics"]["refresh_share"]["value"]
    assert share == pytest.approx(
        100.0 * roles["refresh"]["operations"] / last["attempted"])


def test_an_answer_of_the_state_before_is_not_correct(capsys, monkeypatch):
    """One stream answer replaced by the rows as loaded, once lo has moved
    past 0 (the tenth Q6: of streams that keep step with the refresher
    while cold, after two commits at the least): `correct` false, one operation wrong."""
    from tidb_tpu.client import Client
    real = Client.query
    seen = {"q6": 0, "base": None}

    def query(self, sql, *a, **kw):
        out = real(self, sql, *a, **kw)
        if "l_discount >= 0.05" in sql or "BETWEEN" in sql.upper():
            seen["q6"] += 1
            if seen["base"] is None:
                seen["base"] = out
            elif seen["q6"] == 10:
                return seen["base"]
        return out

    monkeypatch.setattr(Client, "query", query)
    rc, lines = rehearse(capsys, 0)
    assert seen["q6"] >= 10, "the rehearsal sent too few Q6 to alter one"
    compared = next(ln for ln in lines if ln.get("phase") == "compared")
    assert compared["operations_wrong"] \
        + compared["setup_operations_wrong"] == 1
    assert lines[-1]["correct"] is False


def test_the_probe_raises_where_a_read_behind_is_rebuilt(monkeypatch):
    """The parent's behaviour, put back by hand: no generation is kept, so
    the read AS OF the older snapshot is the counted rebuild beside."""
    from tidb_tpu.executor import device_cache
    from tidb_tpu.session import Engine
    ds = load("datasets", "tpch_throughput")
    monkeypatch.setattr(ds, "PROBE_ROWS", 8192)
    eng = Engine()
    try:
        assert ds.require_older_snapshot(eng)["probe"]["kept_reads"] >= 1
        monkeypatch.setattr(device_cache, "KEPT_GENERATIONS", 0)
        with pytest.raises(RuntimeError, match="BEHIND the device cache"):
            ds.require_older_snapshot(eng)
    finally:
        eng.close()
