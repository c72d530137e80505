"""A lint of BENCHMARK.json against the parts of the contract a file can
show: names, units, arrows, and that every cell's files exist."""

import json
import re

import pytest
from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks"]
    assert 1 <= bench["run_seconds"] <= 51
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= len(bench["workloads"]) <= 24
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_names_and_units(bench):
    groups = [bench["configs"], bench["workloads"],
              bench["end_to_end"] + bench["per_layer"]]
    for group in groups:
        names = [e["name"] for e in group]
        assert len(names) == len(set(names)), names
        assert all(NAME.match(n) for n in names), names
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher"), m
        assert m["source"] in SOURCES, m
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert "setup_s" in [m["name"] for m in bench["end_to_end"]]
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and len(w["why"]) <= 200
        assert w["chips"] in (1, 4)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["source"]) <= 200 and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_arrow_lands_in_every_cell_that_reports_it(bench):
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells) for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e, m
        for cell in m.get("workloads", e2e[m["moves"]]):
            assert cell in e2e[m["moves"]], (m["name"], cell)
    for cell in cells:
        reported = [n for n, ws in e2e.items() if cell in ws]
        assert "setup_s" in reported and len(reported) >= 2
        assert any(cell in m.get("workloads", cells)
                   for m in bench["per_layer"])


def test_every_cells_files_exist(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    used = set()
    for w in bench["workloads"]:
        cfg_entry = configs[w["config"]]
        used.add(w["config"])
        assert cfg_entry["file"].startswith("benchmarks/")
        cfg = json.loads((ROOT / cfg_entry["file"]).read_text())
        assert cfg["name"] == w["config"] and cfg["chips"] == w["chips"]
        assert cfg["source"] == cfg_entry["source"]
        assert sorted(cfg["reduced"]) == sorted(cfg_entry["reduced"])
        assert "assumed" in cfg and "guarantees" in cfg
        assert (BENCH / "datasets" / f"{cfg['dataset']}.py").is_file()
        traffic = json.loads(
            (BENCH / "traffic" / f"{w['traffic']}.json").read_text())
        for op in traffic["ops"]:
            assert (BENCH / "ops" / f"{op['kind']}.py").is_file()
    assert used == set(configs), "a configuration no cell uses"
    for m in bench["end_to_end"]:
        assert (BENCH / "end_to_end" / f"{m['name']}.py").is_file(), m
    for m in bench["per_layer"]:
        assert (BENCH / "layer_metrics" / f"{m['name']}.py").is_file(), m


def test_the_harness_names_no_cell_configuration_mix_or_metric(bench):
    words = {e["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in bench[g]}
    words |= {w["traffic"] for w in bench["workloads"]}
    words.discard("setup_s")        # also the name of a context key
    for f in ("run.py", "meters.py", "stats.py", "trace_reduce.py"):
        text = (BENCH / f).read_text()
        hit = sorted(w for w in words if re.search(
            rf"(?<![A-Za-z0-9_.\-]){re.escape(w)}(?![A-Za-z0-9_\-])", text))
        assert not hit, (f, hit)


def test_peaks_name_their_source():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert all(p["source"] for p in peaks.values())
