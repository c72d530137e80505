"""The nine per-layer entries that read the program's spans, device scopes
and first-touch counters, in test_benchmark_json.py's terms: appended after
what was there, a reader file each, every cell listed, arrows that land; and
readers that find nothing to read (and do not raise) over a program that has
no such span or counter."""

import importlib.util
import json

import pytest
from conftest import BENCH, ROOT

NEW = ["wire_ms_per_op", "plan_ms_per_op", "dispatch_ms_per_launch",
       "device_wait_ms_per_op", "span_uncovered_ms_per_op",
       "decode_device_share", "agg_device_share", "unscoped_device_share",
       "setup_encode_s"]
SPAN, SCOPE = NEW[:5], NEW[5:8]


@pytest.fixture(scope="module")
def bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def reader(name):
    path = BENCH / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"lm_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_nine_are_appended_with_every_cell(bench):
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-len(NEW):] == NEW
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"][-len(NEW):]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["workloads"] == cells and m["moves"] in e2e
        assert m["better"] == "lower"
        assert (BENCH / "layer_metrics" / f"{m['name']}.py").is_file()
    by = {m["name"]: m for m in bench["per_layer"]}
    assert {by[n]["source"] for n in SPAN} == {"program_span"}
    assert {by[n]["source"] for n in SCOPE} == {"device_trace"}
    assert by["setup_encode_s"]["source"] == "program_counter"
    assert by["setup_encode_s"]["moves"] == "setup_s"
    assert {by[n]["unit"] for n in SCOPE} == {"%"}


def test_every_new_layer_is_in_perf_md(bench):
    perf = (ROOT / "PERF.md").read_text()
    for m in bench["per_layer"][-len(NEW):]:
        assert f"`{m['name']}`" in perf, m["name"]
        assert m["layer"] in perf, m["layer"]


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_spans_gives_nothing_to_read(
        name, monkeypatch):
    """What the parent commit is to these readers: no `last_events`, no
    profile directory, no first-touch counter."""
    import device_scopes
    import span_reduce
    from tidb_tpu.util import observability, timeline
    monkeypatch.delattr(timeline, "last_events", raising=False)
    monkeypatch.setattr(device_scopes, "newest_profile", lambda: None)
    monkeypatch.setattr(observability, "REGISTRY", observability.Registry())
    ctx = {"latencies_s": [0.1], "trace": {"busy_s": 1.0}, "attempted": 1}
    assert reader(name).read(ctx) is None
