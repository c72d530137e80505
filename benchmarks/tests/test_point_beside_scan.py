"""The HTAP deployment's cell through `run.py` itself, at a rehearsal scale
on whatever device JAX has: the result line is `correct` and a traced
rehearsal reports the five metrics this cell added; a point answer altered
in the wire client, and a Q1 answer altered there, each come out as not
correct."""

import importlib.util
import json

import run
from conftest import BENCH, ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = next(w["name"] for w in SPEC["workloads"]
            if w["traffic"] == "point-beside-scan")
NEW = {"point_stmt_ms", "index_ms_per_point", "plan_miss_share",
       "point_parse_ms", "scan_stmt_ms"}
SEED = "2147483659"


def dataset():
    spec = importlib.util.spec_from_file_location(
        "bench_tests_tpch_htap", BENCH / "datasets" / "tpch_htap.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def rehearse(capsys, trace, seconds="3"):
    rc = run.main(["--workload", CELL, "--seed", SEED, "--seconds", seconds,
                   "--trace", str(trace), "--rehearsal-scale", "0.02"])
    out = capsys.readouterr().out.strip().splitlines()
    return rc, [json.loads(ln) for ln in out]


def test_the_traced_rehearsal_is_correct_and_reports_the_five(capsys):
    rc, lines = rehearse(capsys, 1, seconds="4")
    last = lines[-1]
    assert rc == 0 and last["correct"] is True and last["failed"] == 0
    want = {m["name"] for m in SPEC["per_layer"] if CELL in m["workloads"]}
    assert NEW <= want
    # no peak to divide by on the CPU
    assert set(last["metrics"]) == want - {"scan_hbm_share"}
    m = {k: v["value"] for k, v in last["metrics"].items()}
    assert 0 < m["plan_miss_share"] <= 100
    assert 0 < m["index_ms_per_point"] < m["point_stmt_ms"]
    assert 0 < m["point_parse_ms"] < m["point_stmt_ms"]
    assert m["scan_stmt_ms"] > m["point_stmt_ms"]
    assert m["compiles_in_window"] == 0 and m["h2d_bytes_per_op"] == 0
    roles = next(ln for ln in lines if ln.get("phase") == "roles")
    assert set(roles) == {"phase", "point", "scan"}
    window = next(ln for ln in lines if ln.get("phase") == "window")
    assert roles["point"]["operations"] + roles["scan"]["operations"] == \
        window["attempted"]
    # seven connections of one statement against one: ≥ 95% point reads
    assert roles["point"]["operations"] >= 0.95 * window["attempted"]
    spans = next(ln for ln in lines if ln.get("phase") == "point_spans")
    assert spans["scans"] == roles["scan"]["operations"]
    compared = next(ln for ln in lines if ln.get("phase") == "compared")
    # first touch (one operation: Q1 and a point read) + 20 cycles × 8
    assert compared["setup_operations_compared"] == 161


def test_an_altered_point_answer_is_not_correct(capsys, monkeypatch):
    from tidb_tpu.client import Client
    real = Client.execute_prepared
    state = {"answers": 0}

    def execute_prepared(self, stmt, params=()):
        rows = real(self, stmt, params)
        state["answers"] += 1
        if state["answers"] == 400:     # past warm-up (141), in the window
            (date, prio, cust), = rows
            rows = [(date, prio, cust + 1)]
        return rows

    monkeypatch.setattr(Client, "execute_prepared", execute_prepared)
    rc, lines = rehearse(capsys, 0)
    last = lines[-1]
    assert state["answers"] >= 400
    assert rc == 0 and last["correct"] is False and last["failed"] == 1


def test_an_altered_q1_answer_is_not_correct(capsys, monkeypatch):
    """(The float64 control cannot stand in here: at a rehearsal's scale
    every sum is exact in a float64. `control.py` runs it at the cell's
    own size.)"""
    from tidb_tpu.client import Client
    ds = dataset()
    real = Client.query
    state = {"answers": 0}

    def query(self, sql):
        names, rows = real(self, sql)
        if sql == ds.STATEMENTS[ds.SCAN]:
            state["answers"] += 1
            if state["answers"] == 23:  # first touch + 20 warm cycles + 2
                rows = [tuple(r) for r in rows]
                rows[2] = rows[2][:-1] + (str(int(rows[2][-1]) + 1),)
        return names, rows

    monkeypatch.setattr(Client, "query", query)
    rc, lines = rehearse(capsys, 0)
    last = lines[-1]
    compared = next(ln for ln in lines if ln.get("phase") == "compared")
    assert state["answers"] >= 23
    assert rc == 0 and last["correct"] is False and last["failed"] == 1
    assert compared["setup_operations_wrong"] == 0
    assert compared["first_wrong"][0].startswith("point_beside_scan: rows")
