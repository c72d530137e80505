"""The span recorder from packet-in to last byte out, the names the device
programs and their stages carry, and the benchmark's reductions that read
them (`benchmarks/span_reduce.py`, `benchmarks/device_scopes.py`: pure
arithmetic, so tier-1 checks it on synthetic input)."""

import gc
import json
import os
import re
import sys

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import device_scopes  # noqa: E402
import span_reduce  # noqa: E402

from tidb_tpu.client import Client  # noqa: E402
from tidb_tpu.server import Server  # noqa: E402
from tidb_tpu.session import Engine  # noqa: E402
from tidb_tpu.util import timeline  # noqa: E402


# ---- self-time arithmetic on a synthetic nest ------------------------------

def ev(cat, ts, dur, id_=None, parent=0, req=1, pid=1, name=None):
    args = {"req": req, "parent": parent}
    if id_ is not None:
        args["id"] = id_
    return {"name": name or cat, "cat": cat, "ph": "X", "ts": float(ts),
            "dur": float(dur), "pid": pid, "tid": 1, "args": args}


NEST = [
    ev("stmt", 0, 1000, id_=1),
    ev("wire", 0, 50, id_=2, parent=1),
    ev("parse", 50, 100, id_=3, parent=1),
    ev("frag", 200, 700, id_=4, parent=1),
    # two children that overlap by 50 us and one recorded leaf (no id)
    ev("launch", 250, 100, id_=5, parent=4),
    ev("drain", 300, 400, id_=6, parent=4),
    ev("sched", 260, 20, parent=5),
    ev("wire", 900, 80, id_=7, parent=1),
    ev("client", 1000, 500, id_=8, req=0),
]


@pytest.mark.parametrize("lane,want_us", [
    ("stmt", 1000 - 50 - 100 - 700 - 80),   # what no child covers
    ("frag", 700 - 450),                    # children's UNION, not their sum
    ("launch", 100 - 20),                   # a recorded leaf is a child too
    ("drain", 400), ("parse", 100), ("sched", 20)])
def test_self_time_is_duration_less_the_union_of_children(lane, want_us):
    got = {}
    for e, s in span_reduce.self_times(NEST):
        got[e["cat"]] = got.get(e["cat"], 0.0) + s
    assert got[lane] == pytest.approx(want_us * 1e-6)
    assert got["wire"] == pytest.approx(130e-6)


def test_a_child_is_clipped_to_its_parent():
    nest = [ev("stmt", 0, 100, id_=1), ev("fetch", 90, 50, id_=2, parent=1)]
    got = dict((e["cat"], s) for e, s in span_reduce.self_times(nest))
    assert got["stmt"] == pytest.approx(90e-6)
    assert got["fetch"] == pytest.approx(50e-6)


def test_reduce_keeps_the_requests_that_ran_a_fragment():
    other = [ev("stmt", 2000, 300, id_=20, req=2, pid=9),      # no fragment
             ev("plan", 2010, 100, id_=21, parent=20, req=2, pid=9),
             ev("client", 2300, 900, id_=22, req=0, pid=9)]
    got = span_reduce.reduce(NEST + other)
    assert got["ops"] == 1 and got["launches"] == 1
    assert got["stmt_s"] == pytest.approx(1000e-6)
    assert got["client_s"] == pytest.approx(500e-6)     # conn 1's wait only
    assert "plan" not in got["self_s"]
    # siblings that overlap (50 us here) are each counted in full
    assert sum(got["self_s"].values()) == pytest.approx(1050e-6)
    assert span_reduce.reduce(other) is None
    assert span_reduce.reduce([]) is None


def test_window_finds_nothing_to_read_without_spans(monkeypatch, capsys):
    monkeypatch.setattr(timeline, "last_events", lambda: [])
    assert span_reduce.lanes_ms({"latencies_s": [0.1]}, ("wire",)) is None
    assert capsys.readouterr().out == ""


# ---- device scopes: names on op_name paths ---------------------------------

@pytest.mark.parametrize("op_name,scope,program", [
    ("jit(partial_fused_ab12cd34)/jit(main)/decode/shift_right_logical",
     "decode", "partial_fused_ab12cd34"),
    ("jit(finalize_0123abcd)/jit(main)/finalize/merge/reduce_sum",
     "merge", "finalize_0123abcd"),
    ("jit(finalize_0123abcd)/jit(main)/finalize/sort/sort",
     "sort", "finalize_0123abcd"),
    ("jit(partial_chain_9)/jit(main)/agg/jit(_where)/select_n",
     "agg", "partial_chain_9"),
    ("jit(partial_chain_9)/jit(main)/broadcast_in_dim",
     device_scopes.UNSCOPED, "partial_chain_9"),
    # a stage name as the operation itself is not a scope
    ("jit(x)/sort", device_scopes.UNSCOPED, "x"),
    ("", device_scopes.UNSCOPED, "")])
def test_scope_is_the_innermost_stage_on_the_path(op_name, scope, program):
    assert device_scopes.scope_of(op_name) == scope
    assert device_scopes.program_of(op_name) == program


def test_program_falls_back_to_the_module_name():
    assert device_scopes.program_of("", "jit_merge_0a0a0a0a(1234)") \
        == "merge_0a0a0a0a"
    assert device_scopes.program_of("", "jit__partial") == "_partial"


def test_device_self_seconds_count_a_nested_operation_once():
    # a while loop 0-10 with two body operations, then a lone fusion
    line = [(0.0, 10.0, "while"), (1.0, 4.0, "body.a"), (5.0, 9.0, "body.b"),
            (10.0, 12.0, "fusion")]
    got = dict(device_scopes.self_seconds(line))
    assert got == {"while": pytest.approx(3.0), "body.a": pytest.approx(3.0),
                   "body.b": pytest.approx(4.0),
                   "fusion": pytest.approx(2.0)}
    assert sum(got.values()) == pytest.approx(12.0)


def test_stage_list_is_the_programs():
    from tidb_tpu.executor import device_emit
    assert device_scopes.STAGES == device_emit.STAGES


# ---- the recorder itself ---------------------------------------------------

@pytest.fixture()
def served():
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE sp (a BIGINT PRIMARY KEY, b BIGINT, c BIGINT)")
    s.execute("INSERT INTO sp VALUES " +
              ",".join(f"({i},{i % 5},{i * 3})" for i in range(3000)))
    srv = Server(eng, port=0).start()
    cli = Client(port=srv.port)
    cli.execute("SET tidb_tpu_engine = 'on'")
    cli.execute("SET tidb_tpu_row_threshold = 1")
    try:
        yield eng, srv, cli
    finally:
        cli.close()
        srv.stop()
        eng.close()


AGG = "SELECT b, COUNT(*), SUM(c) FROM sp GROUP BY b"


def test_one_statement_over_the_wire_is_one_covered_root(served, tmp_path):
    """Exactly one `stmt` root per request; every span of the request
    carries its id; the root's children cover it to within 1 ms; the lanes
    run from wire.read to wire.write; the server's wait is a `client`
    span outside any request."""
    eng, srv, cli = served
    for _ in range(2):
        cli.query(AGG)                      # compile, plan cache, imports
    timeline.start_global(str(tmp_path))
    try:
        for _ in range(3):
            assert len(cli.query(AGG)[1]) == 5
        # a root is recorded after its response was sent: the answer to
        # a ping (no root of its own) says the third one is there
        cli.ping()
    finally:
        timeline.stop_global()
    evs = [e for e in timeline.last_events() if e["ph"] == "X"]
    roots = [e for e in evs if e["cat"] == "stmt"]
    assert len(roots) == 3
    assert len({e["args"]["req"] for e in roots}) == 3
    uncovered = []
    for root in roots:
        rid = root["args"]["req"]
        mine = [e for e in evs if e["args"].get("req") == rid]
        assert [e for e in mine if e["cat"] == "stmt"] == [root]
        assert {"wire", "parse", "plan", "exec", "frag", "launch", "drain",
                "fetch", "decode"} <= {e["cat"] for e in mine}
        assert {e["pid"] for e in mine} == {root["pid"]}
        lo, hi = root["ts"], root["ts"] + root["dur"]
        assert all(lo - 1 <= e["ts"] and e["ts"] + e["dur"] <= hi + 1
                   for e in mine)
        ids = {e["args"]["id"] for e in mine if "id" in e["args"]}
        assert all(e["args"]["parent"] in ids for e in mine
                   if e is not root)
        self_s = dict((id(e), s) for e, s in span_reduce.self_times(mine))
        uncovered.append(self_s[id(root)])
        names = [e["name"] for e in sorted(mine, key=lambda e: e["ts"])
                 if e["cat"] == "wire"]
        assert names == ["wire.read", "wire.write"]
    assert min(uncovered) < 1e-3, uncovered
    waits = [e for e in evs if e["cat"] == "client"]
    assert waits and all(e["args"]["req"] == 0 for e in waits)
    got = span_reduce.reduce(evs)
    assert got["ops"] == 3 and got["launches"] == 3
    assert sum(got["self_s"].values()) == pytest.approx(got["stmt_s"],
                                                       abs=5e-6)


def test_statements_of_one_command_share_the_request(served):
    eng, srv, cli = served
    s = eng.new_session()
    with timeline.capture() as cap:
        s.execute("SELECT 1; SELECT 2")
    evs = [e for e in cap.events if e["ph"] == "X"]
    assert len([e for e in evs if e["cat"] == "stmt"]) == 1
    assert len([e for e in evs if e["name"] == "planner.optimize"]) == 2
    assert len({e["args"]["req"] for e in evs}) == 1
    # the next command is another request
    with timeline.capture() as cap2:
        s.execute("SELECT 3")
    assert cap2.events[0]["args"]["req"] != evs[0]["args"]["req"]


def test_plan_cache_lookup_is_tagged(served):
    eng, srv, cli = served
    s = eng.new_session()
    sql = "SELECT b FROM sp WHERE a = 7"
    tags = []
    for _ in range(3):      # (auto-analyze may re-key the plan once)
        with timeline.capture() as cap:
            s.query(sql)
        tags += [e["args"].get("cache") for e in cap.events
                 if e["name"] == "planner.optimize"]
    assert tags[0] == "miss" and tags[-1] == "hit"


SHAPES = {
    "agg": AGG,
    "filter": "SELECT a, c FROM sp WHERE b = 3 AND c > 100",
    "topn": "SELECT a, c FROM sp ORDER BY c DESC LIMIT 7",
    "global": "SELECT SUM(c), COUNT(*) FROM sp WHERE c > 10",
    "join": "SELECT x.b, COUNT(*) FROM sp x JOIN sp y ON x.a = y.a "
            "GROUP BY x.b",
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_launch_spans_equal_programs_launched(served, shape):
    eng, srv, cli = served
    s = eng.new_session()
    s.vars.update({"tidb_tpu_engine": "on", "tidb_tpu_row_threshold": 1,
                   "tidb_tpu_max_slab_rows": 1024})
    with timeline.capture() as cap:
        s.query(SHAPES[shape])
    ph = s.last_guard.phases
    assert s.last_engine == "tpu" and ph.programs_launched >= 1
    launches = [e for e in cap.events if e["cat"] == "launch"]
    assert len(launches) == ph.programs_launched
    for e in launches:
        assert e["name"] == e["args"]["program"]
        kind, _, sig8 = e["name"].rpartition("_")
        assert kind in ("partial_chain", "partial_fused", "merge",
                        "finalize", "tree", "batched") and len(sig8) == 8
    # the seconds ledger keeps its meaning: compute = launches + waits + glue
    spans_s = sum(e["dur"] for e in cap.events if e["cat"] in (
        "launch", "drain") or e["name"] == "frag.glue") * 1e-6
    assert spans_s <= ph.seconds["compute"] + 1e-4
    assert spans_s >= 0.5 * ph.seconds["compute"]


def test_trace_rows_do_not_change_with_the_timeline_on(served, tmp_path):
    eng, srv, cli = served
    s = eng.new_session()
    s.vars.update({"tidb_tpu_engine": "on", "tidb_tpu_row_threshold": 1})

    def labels():
        # the operation's name at its depth; the tags hold timings
        return [re.match(r"\s*(?:└─)?[\w.]+", r[0]).group(0)
                for r in s.query("TRACE " + AGG).rows]

    labels()                                    # warm
    off = labels()
    timeline.start_global(str(tmp_path))
    try:
        on = labels()
    finally:
        timeline.stop_global()
    assert on == off
    assert off[0] == "trace" and any("planner.optimize" in x for x in off)
    assert any("device.fragment" in x for x in off)
    # and the same sites recorded to the timeline under the same names
    names = {e["name"] for e in timeline.last_events()}
    assert {"planner.optimize", "executor.build", "executor.run",
            "device.fragment"} <= names


def test_flush_is_periodic_and_the_stopped_events_stay_readable(
        served, tmp_path, monkeypatch):
    eng, srv, cli = served
    s = eng.new_session()
    s.execute(f"SET tidb_tpu_trace_dir = '{tmp_path}'")
    try:
        s.query("SELECT 1")
        path = timeline.global_path()
        assert path and not os.path.exists(path), \
            "the session wrote the file after a statement"
        monkeypatch.setattr(timeline, "_NEXT_FLUSH", 0.0)
        s.query("SELECT 2")                     # the interval has passed
        assert os.path.exists(path)
        n_file = len(json.load(open(path))["traceEvents"])
        s.query("SELECT 3")
        assert len(json.load(open(path))["traceEvents"]) == n_file
        n_live = len(timeline.last_events())
    finally:
        s.vars["tidb_tpu_trace_dir"] = ""
        timeline.stop_global()
    assert timeline.ENABLED is False
    assert len(timeline.last_events()) >= n_live > 0
    doc = json.load(open(path))                 # stop wrote everything
    assert len([e for e in doc["traceEvents"] if e["ph"] != "M"]) \
        == len(timeline.last_events())
    timeline.start_global(str(tmp_path))
    try:
        assert timeline.last_events() == []
    finally:
        timeline.stop_global()


def test_generation_two_collections_are_gc_spans(tmp_path):
    timeline.start_global(str(tmp_path))
    try:
        assert timeline._gc_event in gc.callbacks
        gc.collect()
    finally:
        timeline.stop_global()
    assert timeline._gc_event not in gc.callbacks
    spans = [e for e in timeline.last_events() if e["cat"] == "gc"]
    assert spans and spans[0]["name"] == "gc.gen2" and spans[0]["dur"] >= 0


def test_first_touch_counters_are_in_engine_metrics(served):
    eng, srv, cli = served
    s = eng.new_session()
    s.execute("CREATE TABLE ft (k BIGINT PRIMARY KEY, s VARCHAR(8), "
              "v BIGINT)")
    s.execute("INSERT INTO ft VALUES " + ",".join(
        f"({i}, 'n{i % 7}', {i % 11})" for i in range(2000)))
    s.vars.update({"tidb_tpu_engine": "on", "tidb_tpu_row_threshold": 1})

    def stages():
        rows = s.query("SELECT LABELS, VALUE FROM "
                       "information_schema.engine_metrics WHERE METRIC = "
                       "'tidb_tpu_first_touch_seconds_total'").rows
        return {lb.partition("=")[2]: float(v) for lb, v in rows}

    before = stages()
    s.query("SELECT s, SUM(v) FROM ft GROUP BY s")      # first touch
    cold = stages()
    assert set(cold) >= {"materialize", "layout", "dict", "pack", "upload"}
    assert all(cold[k] > before.get(k, 0.0) for k in
               ("materialize", "layout", "dict", "pack", "upload"))
    s.query("SELECT s, SUM(v) FROM ft GROUP BY s")      # warm: nothing
    assert stages() == cold
    # the benchmark's reader sums the four encode stages
    sys.path.insert(0, os.path.join(BENCH, "layer_metrics"))
    import setup_encode_s
    assert setup_encode_s.read({}) == pytest.approx(
        sum(cold[k] for k in setup_encode_s.STAGES))


# ---- what the chip must not notice of the aggregate driver ------------------

def _slab_session():
    eng = Engine()
    eng.global_vars["tidb_enable_auto_analyze"] = False
    s = eng.new_session()
    s.execute("CREATE TABLE gd (id INT, name VARCHAR(16))")
    s.execute("INSERT INTO gd VALUES " + ",".join(
        f"({i}, 'name{i:02d}')" for i in range(8)))
    s.execute("CREATE TABLE gf (k BIGINT, b INT, v BIGINT)")
    s.execute("INSERT INTO gf VALUES " + ",".join(
        f"({i % 300}, {i % 8}, {(i * 37) % 211 - 100})"
        for i in range(3000)))
    s.execute("ANALYZE TABLE gd")
    s.execute("ANALYZE TABLE gf")
    s.vars.update({"tidb_tpu_engine": "on", "tidb_tpu_row_threshold": 1,
                   "tidb_tpu_max_slab_rows": 1024})      # three slabs
    return eng, s


_JOIN = "FROM gf f JOIN gd d ON f.b = d.id "
# What an aggregate over three slabs shows the span recorder. Its FIRST
# execution launches a program a slab and the merge or finalize (program,
# slab, `sig` tag, in order); once the digest is specialized a statement the
# driver can run whole is ONE launch, `stmt_<kind>_<sig8>` (`launch_plan`
# `whole`: no `frag.merge` span, there is no merge launch), and one it cannot
# (DISTINCT pair sets, sorted runs) launches the same programs again. Beside
# them the tags of the `device.fragment` and `frag.merge` spans and the
# `jax.device_get` calls. The program names are digests of the compile-cache
# signatures, which key the persistent compile cache on the chip: a change to
# the driver that is not meant to recompile anything leaves them byte for
# byte.
_SLOTS = {"slots_out": 9, "slots_in": 27}
_RUNS = {"slots_in": 0, "slots_out": 1024, "rows": 3072}


def _frag(root, key_bytes, state_bytes=24, grouping="bounds", gcap=9,
          groups=8, plan="whole"):
    return {"root": root, "spec": "hit", "grouping": grouping, "gcap": gcap,
            "rows_in": 3000, "groups": groups, "key_bytes": key_bytes,
            "state_bytes": state_bytes, "launch_plan": plan}


# case → (statement, slab program, its `sig` tag, the launches after the
# slabs, the ONE warm launch or None where the slabs launch again,
# `device.fragment` tags, `frag.merge` tags, device_gets)
PINNED = {
    "chain": (
        "SELECT b, COUNT(*), SUM(v) FROM gf GROUP BY b",
        "partial_chain_7b01d280", None, [("merge_7b01d280", None)],
        ("stmt_chain_fac53939", "stmt:fac53939afd1"),
        _frag("HashAgg", 9), _SLOTS, 1),
    "chain-topn": (
        "SELECT b, COUNT(*), SUM(v) FROM gf GROUP BY b "
        "ORDER BY SUM(v) DESC LIMIT 3",
        "partial_chain_7b01d280", None,
        [("finalize_94637dac", "fused-final:94637dac7685")],
        ("stmt_chain_d3fbc7c6", "stmt:d3fbc7c6433c"),
        _frag("TopN", 9), _SLOTS, 1),
    "chain-distinct": (
        "SELECT b, COUNT(DISTINCT v) FROM gf GROUP BY b ORDER BY b",
        "partial_chain_3379760e", None,
        [("finalize_d4301d6a", "fused-final:d4301d6a8439")], None,
        _frag("Sort", 9, 8, plan="slabs:pairs"), _SLOTS, 3),
    "tree": (
        "SELECT d.name, COUNT(*), SUM(f.v) " + _JOIN + "GROUP BY d.name",
        "partial_fused_efb25ddf", "fused:efb25ddf5808",
        [("merge_aeb0628b", None)],
        ("stmt_fused_648bceef", "stmt:648bceef2d8a"),
        _frag("HashAgg", 5), _SLOTS, 1),
    "tree-sort": (
        "SELECT d.name, COUNT(*), SUM(f.v) " + _JOIN +
        "GROUP BY d.name ORDER BY d.name",
        "partial_fused_efb25ddf", "fused:efb25ddf5808",
        [("finalize_4894e43c", "fused-final:4894e43cbfe4")],
        ("stmt_fused_6ea650cf", "stmt:6ea650cf7368"),
        _frag("Sort", 5), _SLOTS, 1),
    "tree-distinct": (
        "SELECT d.name, COUNT(DISTINCT f.v) " + _JOIN +
        "GROUP BY d.name ORDER BY d.name",
        "partial_fused_28ce7b9a", "fused:28ce7b9ae17f",
        [("finalize_c28bcac4", "fused-final:c28bcac4ba4c")], None,
        _frag("Sort", 5, 8, plan="slabs:pairs"), _SLOTS, 3),
    "runs-chain": (
        "SELECT k, COUNT(*), SUM(v) FROM gf GROUP BY k "
        "ORDER BY SUM(v) DESC, k LIMIT 5",
        "partial_chain_327230ad", None,
        [("sort_rows_2a418386", None),
         ("finalize_31666253", "fused-final:31666253c2ee")], None,
        _frag("TopN", 9, grouping="runs", gcap=1024, groups=300,
              plan="slabs:runs"), _RUNS, 1),
    "runs-tree": (
        "SELECT f.k, COUNT(*), SUM(f.v) " + _JOIN + "GROUP BY f.k "
        "ORDER BY SUM(f.v) DESC, f.k LIMIT 5",
        "partial_fused_38c9441f", "fused:38c9441f33af",
        [("sort_rows_2a418386", None),
         ("finalize_69c85889", "fused-final:69c85889d279")], None,
        _frag("TopN", 9, grouping="runs", gcap=1024, groups=300,
              plan="slabs:runs"), _RUNS, 1),
}


@pytest.mark.parametrize("case", sorted(PINNED))
def test_a_warm_aggregate_launches_the_same_programs_in_the_same_order(
        case, monkeypatch):
    from tidb_tpu.executor import agg_slabs, tree_fragment
    from tidb_tpu.ops.jax_env import jax
    sql, slab_prog, slab_sig, tail, whole, frag_tags, merge_tags, \
        device_gets = PINNED[case]
    by_slab = [(slab_prog, i, slab_sig) for i in range(3)] + \
        [(name, None, sig) for name, sig in tail]
    if frag_tags["grouping"] == "runs":
        monkeypatch.setattr(tree_fragment, "SLOT_ADDRESS_CAP", 64)
    eng, s = _slab_session()

    def launches(cap):
        evs = sorted((e for e in cap.events if e["ph"] == "X"),
                     key=lambda e: e["ts"])
        return evs, [(e["name"], e["args"]["slab"], e["args"].get("sig"))
                     for e in evs if e["cat"] == "launch"]

    try:
        want = eng.new_session().query(sql).rows
        agg_slabs._SPEC_CACHE.clear()        # the first execution of a digest
        with timeline.capture() as cap:
            s.query(sql)
        assert launches(cap)[1] == by_slab
        s.query(sql)                        # specialized: compiles `whole`
        calls = []
        real = jax.device_get
        with monkeypatch.context() as m, timeline.capture() as cap:
            m.setattr(jax, "device_get",
                      lambda t: calls.append(1) or real(t))
            rows = s.query(sql).rows
    finally:
        eng.close()
    assert s.last_engine == "tpu"
    if "ORDER BY" not in sql:
        rows, want = sorted(rows), sorted(want)
    assert rows == want
    evs, got = launches(cap)

    def tags(name):
        (e,) = [e for e in evs if e["name"] == name]
        return {k: v for k, v in e["args"].items()
                if k not in ("req", "id", "parent", "conn", "cpu")}

    assert tags("device.fragment") == frag_tags
    if whole is not None:
        assert got == [(whole[0], None, whole[1])]
        assert not [e for e in evs if e["name"] == "frag.merge"]
    else:
        assert got == by_slab
        assert tags("frag.merge") == merge_tags
    assert len(calls) == device_gets
    assert len([e for e in evs if e["cat"] == "fetch"]) == device_gets
    assert len([e for e in evs if e["cat"] == "drain"]) == 1
    assert not [e for e in evs if e["name"] == "ladder.retry"]


# ---- names on the device ---------------------------------------------------

def test_lowered_programs_carry_their_name_and_their_stages(monkeypatch):
    """The HLO of a chain partial over compressed columns: module
    `jit_partial_chain_<sig8>`, operations under `decode`, `filter` and
    `agg` — the one-hot contraction of the slot sums among them (forced
    onto this small table: the threshold is a constant of `ops/segment.py`)
    — and the fused finalize holds `merge` and `sort` under `finalize`."""
    from tidb_tpu.executor import device_cache as dc
    from tidb_tpu.executor import agg_slabs, compile_cache
    from tidb_tpu.ops import jax_env
    from tidb_tpu.ops import segment as seg
    monkeypatch.setattr(seg, "SLOT_SUM_MIN_WORK", 2)
    seen = {}
    real = jax_env.named_jit

    def recording(fn, name, **kw):
        jitted = real(fn, name, **kw)

        def call(*args):
            seen.setdefault(name, (jitted, args))
            return jitted(*args)
        return call

    monkeypatch.setattr(jax_env, "named_jit", recording)
    compile_cache._COMPILE_CACHE.clear()
    agg_slabs._SPEC_CACHE.clear()
    try:
        eng = Engine()
        s = eng.new_session()
        s.execute("CREATE TABLE z (a BIGINT PRIMARY KEY, b BIGINT, c BIGINT)")
        s.execute("INSERT INTO z VALUES " + ",".join(
            f"({i},{i % 5},{i % 97})" for i in range(4000)))
        s.vars.update({"tidb_tpu_engine": "on", "tidb_tpu_row_threshold": 1,
                       "tidb_tpu_max_slab_rows": 2048})
        s.query("SELECT b, SUM(c) FROM z WHERE c > 3 GROUP BY b "
                "ORDER BY b LIMIT 3")
        assert s.last_engine == "tpu"
        eng.close()
    finally:
        compile_cache._COMPILE_CACHE.clear()
        agg_slabs._SPEC_CACHE.clear()
        dc.clear()
    partial = next(n for n in seen if n.startswith("partial_chain_"))
    final = next(n for n in seen if n.startswith("finalize_"))
    assert len(partial.rpartition("_")[2]) == 8
    jitted, args = seen[partial]
    text = jitted.lower(*args).as_text(debug_info=True)
    assert f"module @jit_{partial} " in text
    for stage in ("decode", "filter", "agg"):
        assert f"jit({partial})/{stage}/" in text, stage
    # ONE contraction, and it lies under `agg`
    assert text.count("stablehlo.dot_general") == 1
    assert re.search(r'jit\(%s\)/agg/[^"]*dot_general' % partial, text)
    jitted, args = seen[final]
    text = jitted.lower(*args).as_text(debug_info=True)
    assert f"module @jit_{final} " in text
    assert f"jit({final})/finalize/merge/" in text
    assert f"jit({final})/finalize/sort/" in text


def test_an_operation_without_a_name_takes_its_operands_scope(tmp_path):
    """`device_scopes.read` over a hand-made profile (the wire format
    written by hand, read back through `xplane_raw`): a reduce-window the
    compiler made has no `tf_op` and takes its operand's scope; a `while`
    and the operation nested in it are each counted once; the program comes
    from the "XLA Modules" line through `program_id`."""
    def varint(n):
        out = b""
        while True:
            b = n & 0x7F
            n >>= 7
            out += bytes([b | (0x80 if n else 0)])
            if not n:
                return out

    def field(num, payload):
        if isinstance(payload, int):
            return varint(num << 3) + varint(payload)
        if isinstance(payload, str):
            payload = payload.encode()
        return varint((num << 3) | 2) + varint(len(payload)) + payload

    stat_names = {1: "tf_op", 2: "program_id"}

    def metadata(mid, name, display, tf_op=None, pid="77"):
        stats = field(5, field(1, 2) + field(5, pid))
        if tf_op:
            stats += field(5, field(1, 1) + field(5, tf_op))
        body = field(1, mid) + field(2, name) + field(4, display) + stats
        return field(4, field(1, mid) + field(2, body))

    def event(mid, start_ps, dur_ps):
        return field(4, field(1, mid) + field(2, start_ps) + field(3, dur_ps))

    ps = 10 ** 12
    prog = "partial_chain_0123abcd"
    plane = field(2, "/device:TPU:0")
    for k, v in stat_names.items():
        plane += field(5, field(1, k) + field(2, field(1, k) + field(2, v)))
    plane += metadata(1, f"jit_{prog}(77)", "", pid="")
    plane += metadata(2, "%unpack = u32[8] fusion(u32[2] %p0), calls=%fc.1",
                      "unpack", f"jit({prog})/decode/shift_right_logical:")
    plane += metadata(3, "%reduce-window.1 = u32[8] reduce-window(u32[8] "
                      "%unpack, u32[] %c), to_apply=%region", "reduce-window.1")
    plane += metadata(4, "%while.2 = (u32[]) while((u32[]) %t), body=%b",
                      "while.2", f"jit({prog})/agg/while:")
    plane += metadata(5, "%fusion.9 = u32[4] fusion(u32[8] %x), calls=%fc.2",
                      "fusion.9", f"jit({prog})/agg/while/body/reduce_sum:")
    plane += metadata(6, "%copy.3 = u32[4] copy(u32[4] %nowhere)", "copy.3")
    plane += field(3, field(2, "XLA Modules") + event(1, 0, 10 * ps))
    plane += field(3, field(2, "XLA Ops") + event(2, 0, 1 * ps)
                   + event(3, 1 * ps, 2 * ps) + event(4, 3 * ps, 6 * ps)
                   + event(5, 4 * ps, 4 * ps) + event(6, 9 * ps, 1 * ps))
    host = field(2, "/host:CPU") + field(
        4, field(1, 1) + field(2, field(1, 1) + field(
            2, "tidb_tpu/stmt/stmt"))) + field(
        3, field(2, "python") + event(1, 0, 5))
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(field(1, plane) + field(1, host))
    got = device_scopes.read(str(path), "tpu")
    assert got["busy_s"] == pytest.approx(10.0)
    assert got["by_scope"] == {
        "decode": pytest.approx(3.0), "agg": pytest.approx(6.0),
        device_scopes.UNSCOPED: pytest.approx(1.0)}
    assert got["inherited_s"] == {"decode": pytest.approx(2.0)}
    assert got["by_program"] == {prog: pytest.approx(10.0)}
    assert got["modules"] == {prog: pytest.approx(10.0)}
    assert got["top_ops"][0] == ["fusion.9", "agg", prog,
                                 pytest.approx(4.0)]
    assert got["annotations"] == 1 and got["op_name_stat"] == "tf_op"
