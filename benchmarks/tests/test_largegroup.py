"""The large-group data set and its cell: the float32 control fails Q3 (and a
float64 does not: every per-group sum is exact in one) — reference against
reference, and put in the program's place under `run.py`'s own comparison —
an altered top-n row comes out as not correct, the traced rehearsal reports
the metrics this cell added, and the bytes function behind `group_hbm_share`
is arithmetic on span tags."""

import importlib.util
import json

import pytest
import run
from conftest import BENCH, ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = next(w["name"] for w in SPEC["workloads"]
            if w["traffic"] == "lgstream1")


def dataset():
    spec = importlib.util.spec_from_file_location(
        "bench_tests_tpch_largegroup",
        BENCH / "datasets" / "tpch_largegroup.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("seed", [1, 2147483659, 2147484201])
def test_the_float32_control_fails_q3(seed):
    ds = dataset()
    data = ds.generate(0.05, seed)
    exact = ds.reference(data)
    lower = ds.reference(data, arithmetic="float32")
    assert lower["Q3"] != exact["Q3"]
    assert ds.reference(data, arithmetic="float64") == exact
    assert [len(exact[q]) for q in ("Q3", "Q10", "Q18")] == [10, 20, 100]


def test_the_same_seed_gives_the_shaped_data_sets_bytes():
    ds = dataset()
    ours, theirs = ds.generate(0.01, 7), ds.base.generate(0.01, 7)
    for table, cols in theirs.items():
        for name, col in cols.items():
            assert (ours[table][name] == col).all(), (table, name)
    price = ours["orders"]["o_totalprice"]
    assert price.min() == ds.PRICE_LO and price.max() == ds.PRICE_HI - 1


def rehearse(capsys, trace, seconds="3"):
    rc = run.main(["--workload", CELL, "--seed", "2147483659", "--seconds",
                   seconds, "--trace", str(trace), "--rehearsal-scale",
                   "0.02"])
    out = capsys.readouterr().out.strip().splitlines()
    return rc, [json.loads(ln) for ln in out]


def test_an_altered_top_n_row_is_not_correct(capsys, monkeypatch):
    from tidb_tpu.client import Client
    real = Client.query
    state = {"answers": 0}

    def query(self, sql):
        names, rows = real(self, sql)
        if "GROUP BY c_custkey\n ORDER BY revenue" in sql:      # Q10
            state["answers"] += 1
            if state["answers"] == 4:       # past warm-up, in the window
                rows = list(rows)
                rows[7], rows[8] = rows[8], rows[7]     # the order only
        return names, rows

    monkeypatch.setattr(Client, "query", query)
    rc, lines = rehearse(capsys, 0)
    last = lines[-1]
    assert state["answers"] >= 4
    assert rc == 0 and last["correct"] is False and last["failed"] == 1


def test_the_float32_control_in_the_programs_place_is_not_correct(
        capsys, monkeypatch):
    """The control through the harness: Q3 answered with the rows the
    reference gives when every SUM is kept in a float32 — the rehearsal's
    own data, so the same seed and scale — and `run.py`'s comparison has
    to say `correct` false for it."""
    from tidb_tpu.client import Client
    ds = dataset()
    lower = ds.reference(ds.generate(0.02, 2147483659),
                         arithmetic="float32")["Q3"]
    real = Client.query
    state = {"answers": 0}

    def query(self, sql):
        names, rows = real(self, sql)
        if sql == ds.STATEMENTS["Q3"]:
            state["answers"] += 1
            rows = [tuple(r) for r in lower]
        return names, rows

    monkeypatch.setattr(Client, "query", query)
    rc, lines = rehearse(capsys, 0)
    last = lines[-1]
    compared = next(ln for ln in lines if ln.get("phase") == "compared")
    assert rc == 0 and last["correct"] is False
    assert last["failed"] == state["answers"] - 3 >= 1   # 3 in set-up
    assert compared["setup_operations_wrong"] == 3
    assert compared["first_wrong"][0].startswith("Q3: rows differ")


def test_the_traced_rehearsal_reports_this_cells_metrics(capsys):
    rc, lines = rehearse(capsys, 1, seconds="4")
    last = lines[-1]
    assert rc == 0 and last["correct"] is True
    want = {m["name"] for m in SPEC["per_layer"] if CELL in m["workloads"]}
    # no peak to divide by on the CPU, and its profile names no scope
    assert set(last["metrics"]) == want - {"group_hbm_share",
                                           "scan_hbm_share"}
    scan = next(ln for ln in lines if ln.get("phase") == "scan_bytes")
    # Q18 reads lineitem twice, and the benchmark's bytes say so
    assert scan["ratio"] == 1.0
    m = {k: v["value"] for k, v in last["metrics"].items()}
    assert m["host_rows_per_op"] <= 100
    assert m["d2h_bytes_per_op"] <= 65536
    assert m["group_retries_per_op"] == 0
    assert m["compiles_in_window"] == 0 and m["h2d_bytes_per_op"] == 0


def test_group_bytes_is_arithmetic_on_span_tags():
    import group_bytes
    frags = [{"args": {"id": 1, "rows_in": 100, "key_bytes": 9,
                       "state_bytes": 32}},
             {"args": {"id": 2, "rows_in": 50, "key_bytes": 18,
                       "state_bytes": 16}}]
    merges = [{"args": {"parent": 1, "slots_in": 10}},
              {"args": {"parent": 7, "slots_in": 99}}]      # no such span
    assert group_bytes.moved_bytes(frags, merges) == \
        2 * (100 * 41 + 50 * 34 + 10 * 41)
