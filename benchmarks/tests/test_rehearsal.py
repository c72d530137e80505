"""Each cell rehearsed at a tiny scale on the CPU, through `run.main`: the
run ends in a well-formed last line; the benchmark's bytes function agrees
with the program's SCAN_BYTES; a timed path that alters one answer comes out
as not correct; off the TPU, without the rehearsal switch, there is no result.
"""

import json

import pytest
import run
from conftest import ROOT

SCALE = "0.01"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


def rehearse(capsys, cell, trace, seconds="2", seed="2147483659"):
    rc = run.main(["--workload", cell, "--seed", seed, "--seconds", seconds,
                   "--trace", str(trace), "--rehearsal-scale", SCALE])
    out = capsys.readouterr().out.strip().splitlines()
    lines = [json.loads(ln) for ln in out]
    return rc, lines


def expected_metrics(cell, group):
    return {m["name"] for m in BENCH[group]
            if cell in m.get("workloads", CELLS)}


@pytest.mark.parametrize("cell", CELLS)
def test_end_to_end_run_ends_in_a_well_formed_line(capsys, cell):
    rc, lines = rehearse(capsys, cell, 0)
    last = lines[-1]
    assert rc == 0 and last["rehearsal"] is True
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(last["metrics"]) == expected_metrics(cell, "end_to_end")
    units = {m["name"]: m["unit"] for m in BENCH["end_to_end"]}
    for name, m in last["metrics"].items():
        assert m["unit"] == units[name] and m["value"] > 0
    assert set(last["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    phases = [ln["phase"] for ln in lines[:-1]]
    for want in ("device", "load", "first_touch", "warm_cycles", "compared",
                 "window", "setup"):
        assert want in phases
    compared = next(ln for ln in lines if ln.get("phase") == "compared")
    assert compared["operations_compared"] == last["attempted"]
    assert compared["limit_wrong"] == 0


@pytest.mark.parametrize("cell", CELLS[:2])
def test_traced_run_reports_the_layer_metrics(capsys, cell):
    rc, lines = rehearse(capsys, cell, 1, seconds="4")
    last = lines[-1]
    assert rc == 0 and last["correct"] is True
    # on the CPU there is no peak to divide by: that reader finds nothing to
    # read and the harness leaves its metric out
    want = expected_metrics(cell, "per_layer") - {"scan_hbm_share"}
    assert set(last["metrics"]) == want
    assert last["metrics"]["compiles_in_window"]["value"] == 0
    assert last["metrics"]["h2d_bytes_per_op"]["value"] == 0
    dev = last["device"]
    assert dev["busy_s"] > 0 and dev["window_s"] > dev["busy_s"]
    assert last["breakdown"]["device_ops"]
    assert len(last["breakdown"]["device_ops"]) <= 10
    assert len(last["breakdown"]["idle_gaps"]) <= 10
    # the bytes function behind scan_hbm_share against the program's counter
    sb = next(ln for ln in lines if ln.get("phase") == "scan_bytes")
    assert sb["counted_by_the_program"] > 0
    assert sb["needed_by_the_benchmark"] == pytest.approx(
        sb["counted_by_the_program"], rel=0.02)


def test_an_altered_answer_is_not_correct(capsys, monkeypatch):
    """The timed path broken underneath: the wire client alters one digit of
    one answer where it is produced. Everything else of a run is driven."""
    from tidb_tpu.client import Client
    real = Client.query
    state = {"answers": 0}

    def query(self, sql):
        names, rows = real(self, sql)
        if sql.lstrip().startswith("SELECT COUNT(*), SUM("):
            state["answers"] += 1
            if state["answers"] == 5:        # past warm-up, in the window
                r = list(rows[0])
                r[-1] = r[-1][:-1] + ("1" if r[-1][-1] != "1" else "2")
                rows = [tuple(r)] + list(rows[1:])
        return names, rows

    monkeypatch.setattr(Client, "query", query)
    rc, lines = rehearse(capsys, CELLS[-1], 0, seconds="3")
    last = lines[-1]
    assert state["answers"] >= 5
    assert rc == 0 and last["correct"] is False and last["failed"] == 1
    compared = next(ln for ln in lines if ln.get("phase") == "compared")
    assert compared["operations_wrong"] == 1 > compared["limit_wrong"]


def test_off_the_tpu_there_is_no_result(capsys):
    rc = run.main(["--workload", CELLS[-1], "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    captured = capsys.readouterr()
    assert rc != 0
    assert "correct" not in captured.out
    assert "needs a TPU" in captured.err
