"""The OLTP half of the HTAP deployment on the normal path: prepared
primary-key point reads of `orders` over the wire WHILE another connection
cycles Q1 through the device path, against the benchmark's plain reference
(`benchmarks/datasets/tpch_htap.py`, which imports nothing of the program).

* 500 seeded keys sent prepared, each answer equal to the reference's plain
  lookup, Q1 equal to the exact reference all the while, ONE index build;
* the data set's probe: raises on a table without its primary key and on a
  client that cannot prepare;
* the operation kind (`benchmarks/ops/point_beside_scan.py`): the Zipf draw
  (seeded, per connection, inside the table, the hot keys' share as the
  harmonic sums say), the role pin, `check` on altered answers;
* the spans and counters the readers use: `index.probe` under a `stmt` root
  tagged `class=interactive proto=binary`, `index.build` once, the plan
  cache's miss counter, and the readers' reduction on a synthetic nest.
"""

import importlib.util
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from tidb_tpu.client import Client
from tidb_tpu.server import Server
from tidb_tpu.session import Engine
from tidb_tpu.util import timeline
from tidb_tpu.util.observability import REGISTRY

BENCH = Path(__file__).resolve().parents[1] / "benchmarks"
SCALE, SEED = 0.02, 2147483693
SETTINGS = dict(tidb_tpu_engine="on", tidb_tpu_row_threshold=1,
                tidb_tpu_strict="on")


def _load(kind: str, name: str):
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    path = BENCH / kind / f"{name}.py" if kind else BENCH / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"tests_bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _counter(name: str, **labels) -> float:
    want = tuple(sorted(labels.items()))
    return sum(v for (n, l), v in REGISTRY.counters.items()
               if n == name and (not labels or l == want))


def _connect(srv) -> Client:
    cli = Client(port=srv.port, auto_reconnect=False)
    for var, value in SETTINGS.items():
        cli.execute(f"SET {var} = {value!r}" if isinstance(value, str)
                    else f"SET {var} = {value}")
    return cli


@pytest.fixture(scope="module")
def ds():
    return _load("datasets", "tpch_htap")


@pytest.fixture(scope="module")
def kind():
    return _load("ops", "point_beside_scan")


@pytest.fixture(scope="module")
def deployment(ds):
    """→ (data, reference, server) with the deployment loaded."""
    data = ds.generate(SCALE, SEED)
    eng = Engine()
    eng.global_vars["tidb_enable_auto_analyze"] = False
    ds.load(eng, data)
    srv = Server(eng, port=0).start()
    yield data, ds.reference(data), srv
    srv.stop()
    eng.close()


# ---------------------------------------------------------------------------
# the system against the plain reference, the two kinds of work at once
# ---------------------------------------------------------------------------

def test_500_prepared_point_reads_beside_a_cycling_device_scan(ds,
                                                               deployment):
    data, ref, srv = deployment
    n_orders = len(data["orders"]["o_orderkey"])
    keys = np.random.default_rng([SEED, 9]).integers(0, n_orders, 20000)
    builds0 = _counter("tidb_tpu_index_builds_total", table="orders")
    probes0 = _counter("tidb_tpu_index_probes_total")
    fallbacks0 = _counter("tidb_tpu_device_fallbacks_total")
    scans, stop, failure = [], threading.Event(), []

    def scan():
        try:
            with _connect(srv) as cli:
                while not stop.is_set() or not scans:
                    scans.append(cli.query(ds.STATEMENTS[ds.SCAN])[1])
        except Exception as e:  # noqa: BLE001 — shown by the main thread
            failure.append(e)

    t = threading.Thread(target=scan)
    t.start()
    try:
        with _connect(srv) as cli:
            stmt = cli.prepare(ds.POINT_STATEMENT)
            assert stmt.n_params == 1
            assert stmt.names == list(ds.POINT_COLUMNS)
            while not scans and not failure:    # the scan's programs exist
                time.sleep(0.01)
            before, got = len(scans), []
            for k in keys:      # 500 reads, and on until the scan cycled
                got.append(cli.execute_prepared(stmt, [int(k)]))
                if len(got) >= 500 and len(scans) - before >= 2:
                    break
            cycled = len(scans) - before
            # a key no order has: no row, from both
            assert cli.execute_prepared(stmt, [n_orders + 5]) == \
                ref[ds.POINT].row(n_orders + 5) == []
            names, rows = cli.query(
                "SELECT * FROM information_schema.statements_summary")
            engine = [d["ENGINE"] for d in (dict(zip(names, r)) for r in rows)
                      if d["DIGEST_TEXT"].lower().startswith(
                          "select l_returnflag")]
    finally:
        stop.set()
        t.join(timeout=60)
    assert not t.is_alive() and not failure, failure
    want = [ref[ds.POINT].row(int(k)) for k in keys[:len(got)]]
    assert got == want
    assert all(len(r) == 1 for r in want)
    # the scan ran on the device all the while, and answered exactly
    assert cycled >= 2, "the scan did not cycle beside the point reads"
    assert all([tuple(r) for r in rows] == ref[ds.SCAN] for rows in scans)
    assert engine == ["tpu"]
    assert _counter("tidb_tpu_device_fallbacks_total") == fallbacks0
    assert _counter("tidb_tpu_index_builds_total", table="orders") \
        - builds0 == 1
    assert _counter("tidb_tpu_index_probes_total") - probes0 == \
        len(got) + 1


def test_the_reference_lookup_is_a_plain_position_lookup(ds, deployment):
    data, ref, _srv = deployment
    o = data["orders"]
    for pos in (0, 1, 17, len(o["o_orderkey"]) - 1):
        (date, prio, cust), = ref[ds.POINT].row(int(o["o_orderkey"][pos]))
        assert date == str(np.datetime64(int(o["o_orderdate"][pos]), "D"))
        assert prio == ds.base.PRIORITIES[o["o_orderpriority"][pos]]
        assert cust == o["o_custkey"][pos] and type(cust) is int
    assert ref[ds.POINT].row(-1) == []
    # the control differs in Q1 alone: a point row holds no arithmetic
    lower = ds.reference(data, arithmetic="float64")
    assert lower[ds.POINT] == ref[ds.POINT]
    assert set(ref) == {ds.SCAN, ds.POINT}


def test_the_probe_raises_on_a_table_without_its_primary_key(ds):
    eng = Engine()
    s = eng.new_session()
    for ddl in ds.SCHEMA:
        s.execute(ddl.replace("o_orderkey BIGINT PRIMARY KEY",
                              "o_orderkey BIGINT"))
    with pytest.raises(RuntimeError, match="index read on PRIMARY"):
        ds.require_point_path(eng)
    eng.close()


def test_the_probe_passes_on_the_schema_and_raises_without_prepare(
        ds, monkeypatch):
    eng = Engine()
    s = eng.new_session()
    for ddl in ds.SCHEMA:
        s.execute(ddl)
    read = ds.require_point_path(eng)["probe"]
    assert any("index:PRIMARY" in r[-1] for r in read["plan"])
    monkeypatch.delattr(Client, "execute_prepared")
    with pytest.raises(RuntimeError, match="cannot send them"):
        ds.require_point_path(eng)
    eng.close()


# ---------------------------------------------------------------------------
# the operation kind: keys, roles, check
# ---------------------------------------------------------------------------

N_KEYS = 200_000


def _streams(kind, seed_word):
    return kind.KeyStreams(np.arange(N_KEYS, dtype=np.int64) * 3 + 11,
                           0.99, seed_word)


def test_the_same_seed_and_connection_give_the_same_keys(kind):
    a, b, other = _streams(kind, 5), _streams(kind, 5), _streams(kind, 6)
    one = [a.next(2) for _ in range(2000)]
    assert one == [b.next(2) for _ in range(2000)]
    assert one != [b.next(3) for _ in range(2000)]      # another connection
    assert one != [other.next(2) for _ in range(2000)]  # another seed
    # a stream goes on past the block drawn ahead
    assert (a.block(2, 1) != a.block(2, 0)).any()


def test_every_key_is_an_order_key_and_the_hot_keys_lie_all_over(kind):
    st = _streams(kind, 5)
    keys = st.block(1, 0)
    assert ((keys - 11) % 3 == 0).all()
    assert keys.min() >= 11 and keys.max() <= 11 + 3 * (N_KEYS - 1)
    # scrambled: the hottest keys are not the lowest ones
    hot = st.perm[:128]
    assert np.median(hot) > 11 + 3 * N_KEYS * 0.2
    assert sorted(st.perm) == list(range(11, 11 + 3 * N_KEYS, 3))


def test_the_128_hottest_keys_draw_their_harmonic_share(kind):
    st = _streams(kind, 7)
    w = np.arange(1, N_KEYS + 1, dtype=np.float64) ** -0.99
    expected = w[:128].sum() / w.sum()
    keys = np.concatenate([st.block(c, 0) for c in (1, 2)])
    share = np.isin(keys, st.perm[:128]).mean()
    assert abs(share - expected) < 0.02
    assert 0.3 < expected < 0.6         # at 200K keys; ≈ 0.30 at 12M


class _FakeClient:
    """What the kind calls of `tidb_tpu.client.Client`."""

    def __init__(self, lookup, scan_rows):
        self.lookup, self.scan_rows = lookup, scan_rows
        self.sent = []

    def query(self, sql):
        self.sent.append("scan")
        return ["x"], self.scan_rows

    def prepare(self, sql):
        self.sent.append("prepare")
        return "handle"

    def execute_prepared(self, stmt, params):
        assert stmt == "handle"
        self.sent.append("point")
        return self.lookup.row(params[0])


def test_the_first_connection_scans_and_seven_read(ds, kind, deployment):
    data, ref, _srv = deployment
    ds.CURRENT.update(data=data, seed=SEED)
    spec = {"kind": "point_beside_scan", "scanners": 1, "readers": 7,
            "zipf": 0.99}
    op = kind.bind(spec, ds, np.random.default_rng([SEED, 2]))
    assert list(op["statements"]) == [ds.SCAN]      # the device's alone
    clients = [_FakeClient(ref[ds.POINT], ref[ds.SCAN]) for _ in range(8)]
    first = kind.run(clients[0], op)
    # first touch: the scan AND one point read, on the scanner
    assert first["role"] == "first" and first["scan"] is not None
    assert len(first["points"]) == 1
    assert clients[0].sent == ["scan", "prepare", "point"]
    answers = [first]
    for _cycle in range(3):
        for cli in clients:
            answers.append(kind.run(cli, op))
    assert clients[0].sent[3:] == ["scan"] * 3
    for cli in clients[1:]:
        assert cli.sent == ["prepare", "point", "point", "point"]
    roles = [a["role"] for a in answers[1:]]
    assert roles == (["scan"] + ["point"] * 7) * 3
    assert all(kind.check(op, a, ref) for a in answers)
    # seven streams, each its own keys
    firsts = {a["points"][0][0] for a in answers[2:9]}
    assert len(firsts) >= 6


def test_an_altered_answer_fails_the_check(ds, kind, deployment):
    data, ref, _srv = deployment
    op = {"dataset": ds}
    key = int(data["orders"]["o_orderkey"][42])
    row = ref[ds.POINT].row(key)
    good = {"role": "point", "scan": None, "points": [(key, row)]}
    assert kind.check(op, good, ref)
    (date, prio, cust), = row
    for bad in ([(date, prio, cust + 1)], [(date, "9", cust)],
                [("1992-01-01x", prio, cust)], [], row * 2,
                [(date, prio, str(cust))]):
        assert not kind.check(op, dict(good, points=[(key, bad)]), ref)
    # the row of another key
    assert not kind.check(op, dict(good, points=[(key + 1, row)]), ref)
    scan = {"role": "scan", "scan": ref[ds.SCAN], "points": []}
    assert kind.check(op, scan, ref)
    altered = [tuple(r) for r in ref[ds.SCAN]]
    altered[0] = altered[0][:-1] + (str(int(altered[0][-1]) + 1),)
    assert not kind.check(op, dict(scan, scan=altered), ref)
    assert not kind.check(op, dict(scan, scan=None), ref)


# ---------------------------------------------------------------------------
# spans and counters
# ---------------------------------------------------------------------------

def test_a_point_read_is_an_index_probe_under_an_interactive_binary_root(
        ds, deployment):
    data, _ref, srv = deployment
    with _connect(srv) as cli:
        stmt = cli.prepare(ds.POINT_STATEMENT)
        cli.execute_prepared(stmt, [3])         # the index exists
        with timeline.capture() as cap:
            cli.execute_prepared(stmt, [5])
            cli.query(ds.STATEMENTS[ds.SCAN])
            cli.query("SELECT 1")   # the roots before it have closed
    spans = [e for e in cap.events if e["ph"] == "X"]
    roots = {e["args"]["req"]: e for e in spans if e["cat"] == "stmt"}
    (probe,) = [e for e in spans if e["name"] == "index.probe"]
    assert probe["cat"] == "index"
    assert probe["args"]["ranges"] == 1 and probe["args"]["rows"] == 1
    root = roots[probe["args"]["req"]]
    assert probe["args"]["req"] > 0
    assert root["args"]["class"] == "interactive"
    assert root["args"]["proto"] == "binary"
    assert root["ts"] <= probe["ts"] and \
        probe["ts"] + probe["dur"] <= root["ts"] + root["dur"] + 1
    (read,) = [e for e in spans if e["name"] == "wire.read"
               and e["args"]["req"] == probe["args"]["req"]]
    assert read["args"]["params"] == 1
    # the scan beside it: a batch statement in the text protocol
    frag = next(e for e in spans if e["cat"] == "frag")
    scan_root = roots[frag["args"]["req"]]
    assert scan_root["args"]["class"] == "batch"
    assert scan_root["args"]["proto"] == "text"
    assert not [e for e in spans if e["name"] == "index.build"]


def test_an_index_is_built_once_a_table_version(ds):
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE o1 (k BIGINT PRIMARY KEY, v BIGINT)")
    s.execute("INSERT INTO o1 VALUES " +
              ",".join(f"({i},{i * i})" for i in range(200)))
    with timeline.capture() as cap:
        for k in (7, 8, 9):
            assert s.execute(f"SELECT v FROM o1 WHERE k = {k}")[0].rows \
                == [(k * k,)]
        s.execute("INSERT INTO o1 VALUES (1000, 1)")    # a new version
        assert s.execute("SELECT v FROM o1 WHERE k = 1000")[0].rows == \
            [(1,)]
    builds = [e for e in cap.events if e["name"] == "index.build"]
    assert [(b["args"]["table"], b["args"]["index"], b["args"]["rows"])
            for b in builds] == [("o1", "k", 200), ("o1", "k", 201)]
    probes = [e for e in cap.events if e["name"] == "index.probe"]
    assert len(probes) == 4
    # the first probe of a version holds its build
    assert builds[0]["args"]["parent"] == probes[0]["args"]["id"]
    # a direct execute is its own root: no wire, so no protocol
    roots = [e for e in cap.events if e["cat"] == "stmt"]
    assert all("proto" not in e["args"] for e in roots)
    assert {e["args"]["class"] for e in roots} == {"interactive", "none"}
    assert _counter("tidb_tpu_index_builds_total", table="o1") == 2
    eng.close()


def test_a_new_literal_is_a_plan_cache_miss_and_is_counted():
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE o2 (k BIGINT PRIMARY KEY, v BIGINT)")
    s.execute("INSERT INTO o2 VALUES (1, 1), (2, 4), (3, 9)")
    hits0 = _counter("tidb_tpu_plan_cache_hits_total")
    miss0 = _counter("tidb_tpu_plan_cache_misses_total")
    for k in (1, 2, 1, 3, 2, 1):
        s.execute(f"SELECT v FROM o2 WHERE k = {k}")
    assert _counter("tidb_tpu_plan_cache_misses_total") - miss0 == 3
    assert _counter("tidb_tpu_plan_cache_hits_total") - hits0 == 3
    s.execute("INSERT INTO o2 VALUES (4, 16)")      # uncacheable: no count
    assert _counter("tidb_tpu_plan_cache_misses_total") - miss0 == 3
    eng.close()


def _x(cat, name, ts, dur, req, id_, parent=0, **tags):
    return {"ph": "X", "cat": cat, "name": name, "ts": ts, "dur": dur,
            "pid": 1, "args": dict(tags, req=req, id=id_, parent=parent)}


def test_the_readers_reduction_on_a_synthetic_nest():
    point_spans = _load("", "point_spans")
    events = [
        # a point read: 1000 us, parse 200, plan 100 (miss), index 50
        _x("stmt", "stmt", 0, 1000, 1, 1, proto="binary",
           **{"class": "interactive"}),
        _x("parse", "parse", 10, 200, 1, 2, 1),
        _x("plan", "planner.optimize", 300, 100, 1, 3, 1, cache="miss"),
        _x("exec", "executor.run", 500, 300, 1, 4, 1),
        _x("index", "index.probe", 600, 50, 1, 5, 4, ranges=1, rows=1),
        # another: 600 us, a hit, index 30
        _x("stmt", "stmt", 2000, 600, 2, 6, proto="binary",
           **{"class": "interactive"}),
        _x("parse", "parse", 2010, 100, 2, 7, 6),
        _x("plan", "planner.optimize", 2200, 20, 2, 8, 6, cache="hit"),
        _x("index", "index.probe", 2300, 30, 2, 9, 6, ranges=1, rows=1),
        # the scan: 40 ms with a fragment; a DML statement of class none
        _x("stmt", "stmt", 0, 40000, 3, 10, proto="text",
           **{"class": "batch"}),
        _x("frag", "device.fragment", 100, 39000, 3, 11, 10),
        _x("stmt", "stmt", 5000, 300, 4, 12, proto="text",
           **{"class": "none"}),
    ]
    got = point_spans.reduce(events)
    assert got["points"] == 2 and got["scans"] == 1
    assert got["point_misses"] == 1
    assert got["point_stmt_s"] == pytest.approx(1600e-6)
    assert got["scan_stmt_s"] == pytest.approx(40000e-6)
    assert got["point_self_s"]["index"] == pytest.approx(80e-6)
    assert got["point_self_s"]["parse"] == pytest.approx(300e-6)
    assert got["point_self_s"]["exec"] == pytest.approx(250e-6)
    # a program whose roots carry no class: nothing to read
    for e in events:
        e["args"].pop("class", None)
    assert point_spans.reduce(events) is None
    ctx = {"attempted": 0, "_span_events": []}
    for name in ("point_stmt_ms", "index_ms_per_point", "plan_miss_share",
                 "point_parse_ms", "scan_stmt_ms"):
        assert _load("layer_metrics", name).read(ctx) is None
