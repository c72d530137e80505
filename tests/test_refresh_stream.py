"""TPC-H's refresh stream on the normal path: RF1 "new sales" and RF2 "old
sales" as small two-table transactions, each read back by Q1, Q3 and Q6,
against the benchmark's plain reference (`benchmarks/datasets/
tpch_refresh.py`, which imports nothing of the program) and the CPU engine.

* twenty `refresh_pair` operations through `Session.execute` and through
  the wire: every answer equal to the reference's state after the
  transaction before it, the four row counts as written, every read on
  the device, no decline, no program trace once the second operation has
  run (a compaction, which re-chooses layouts, apart: it is crossed in the
  middle, and two operations later nothing traces again);
* the `compaction-commit` and `delta-merge-stale` failpoints under the
  stream, as `tests/test_delta_slabs.py` pins them on a toy table;
* the reference against itself: partial sums added and subtracted equal a
  recomputation over the concatenated rows, and the float64 control
  differs once the sums pass 2^53;
* atomicity and freshness: a reader on a second connection between the two
  INSERTs of an open transaction sees neither, and both at once after the
  COMMIT.
"""

import importlib.util
import sys
from pathlib import Path

import numpy as np
import pytest

from tidb_tpu.executor import delta

from tidb_tpu.executor import compile_cache
from tidb_tpu.session import Engine
from tidb_tpu.util import failpoint
from tidb_tpu.util.observability import REGISTRY

BENCH = Path(__file__).resolve().parents[1] / "benchmarks"
SCALE, SEED, K = 0.01, 2147483659, 15


def _load(kind: str, name: str):
    for p in (str(BENCH),):
        if p not in sys.path:
            sys.path.insert(0, p)
    spec = importlib.util.spec_from_file_location(
        f"tests_bench_{kind}_{name}", BENCH / kind / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ds():
    return _load("datasets", "tpch_refresh")


def _counter(name: str) -> float:
    return sum(v for (n, _l), v in REGISTRY.counters.items() if n == name)


def _engine(ds, data):
    eng = Engine()
    eng.global_vars["tidb_enable_auto_analyze"] = False
    ds._bulk_load(eng, data)
    return eng


SETTINGS = dict(tidb_tpu_engine="on", tidb_tpu_row_threshold=1,
                tidb_tpu_strict="on", tidb_tpu_max_slab_rows=16384,
                tidb_tpu_compaction="off")


class _SessionClient:
    """`Session.execute` behind the wire client's two calls."""

    def __init__(self, session):
        self.s = session

    def execute(self, sql):
        return self.s.execute(sql)[0].affected_rows

    def query(self, sql):
        rs = self.s.execute(sql)[0]
        return [], [tuple(None if v is None else str(v) for v in r)
                            for r in rs.rows]


def _reference(ds, data):
    """The data set's reference, for transactions of K orders (the
    configuration's are 150: too many for a table this small)."""
    ref = ds.reference(data)
    ref[ds.STATE] = ds.RefreshState(data, SEED, k=K)
    return ref


def _drive(ds, client, data, n_ops, on_op=None):
    """`n_ops` refresh_pair operations through the benchmark's own kind."""
    kind = _load("ops", "refresh_pair")
    op = kind.bind({"kind": "refresh_pair", "orders": K,
                    "reads": ["Q1", "Q3", "Q6"]}, ds, None)
    ref = _reference(ds, data)
    traces = []
    for n in range(n_ops):
        t0 = compile_cache.PROGRAM_TRACES
        answer = kind.run(client, op)
        traces.append(compile_cache.PROGRAM_TRACES - t0)
        assert answer["n"] == n
        assert kind.check(op, answer, ref), (n, answer)
        if on_op is not None:
            on_op(n)
    return traces, ref


@pytest.mark.parametrize("through", ["session", "wire"])
def test_twenty_refresh_pairs_read_back(ds, through, monkeypatch):
    # compaction due once the delta slab holds half of this many rows
    monkeypatch.setattr(delta, "COMPACT_FILL", 600 / 2048)
    data = ds.generate(SCALE, SEED)
    eng = _engine(ds, data)
    server = None
    if through == "wire":
        from tidb_tpu.client import Client
        from tidb_tpu.server import Server
        server = Server(eng, port=0).start()
        client = Client(port=server.port, auto_reconnect=False)
        for k, v in SETTINGS.items():
            client.execute(f"SET {k} = {v!r}" if isinstance(v, str)
                           else f"SET {k} = {v}")
    else:
        s = eng.new_session()
        s.vars.update(SETTINGS)
        client = _SessionClient(s)
    dec0, fb0 = _counter("tidb_tpu_delta_declines_total"), \
        _counter("tidb_tpu_device_fallbacks_total")
    comp0 = _counter("tidb_tpu_compactions_total")
    compacted_at = []

    warm = {}

    def on_op(n):
        if n == 1:
            warm["dec"] = _counter("tidb_tpu_delta_declines_total")
        if delta.pending_compactions() and not compacted_at:
            assert delta.run_pending_compactions() >= 1
            compacted_at.append(n)

    try:
        traces, _ref = _drive(ds, client, data, 20, on_op)
    finally:
        if server is not None:
            client.close()
            server.stop()
        eng.close()
    assert compacted_at and 2 < compacted_at[0] < 16, compacted_at
    assert _counter("tidb_tpu_compactions_total") > comp0
    c = compacted_at[0]
    quiet = traces[2:c + 1] + traces[c + 3:]
    assert not any(quiet), (compacted_at, traces)
    # the first RF1's order keys lie above every key loaded: the aligned
    # join's lookup table is rebuilt ONCE over the widened bounds (counted,
    # `aligned-key-domain`) and never again — a compaction included, whose
    # warm-up builds the structure over the new row positions
    assert _counter("tidb_tpu_delta_declines_total") == warm["dec"] <= dec0 + 1
    assert _counter("tidb_tpu_device_fallbacks_total") == fb0


def test_the_collector_is_tuned_only_while_a_server_serves():
    """`server._tune_gc`: the first server to start raises the young
    threshold, the last to stop puts back what it found — an embedding
    process keeps its own collector before and after."""
    import gc

    from tidb_tpu import server as srv
    from tidb_tpu.session import Engine
    before = gc.get_threshold()
    eng = Engine()
    a, b = srv.Server(eng, port=0), srv.Server(eng, port=0)
    try:
        assert gc.get_threshold() == before
        a.start()
        assert gc.get_threshold()[0] == max(before[0],
                                            srv.GC_YOUNG_THRESHOLD)
        b.start()
        a.stop()
        a.stop()                                    # (idempotent)
        assert gc.get_threshold()[0] == max(before[0],
                                            srv.GC_YOUNG_THRESHOLD)
    finally:
        b.stop()
        a.stop()
        eng.close()
    assert gc.get_threshold() == before


def test_a_superseded_generation_frees_by_reference_count(ds):
    """Every write makes a new generation of each table it touched; what
    only the old one held (liveness masks, delta slab arrays, the aligned
    join's arrays) has to go when its last reader does — by reference
    count, not whenever the cycle collector happens to run (a server
    raises its young threshold: `server._tune_gc`). With the collector
    off, ten operations leave the device's live bytes where they were
    (`agg_slabs.plan_aligned_joins`' recursive closure used to hold every
    generation it had seen: 14 GB of a 16 GB chip in a 40 s window). The
    cache keeps `KEPT_GENERATIONS` older generations behind each newest
    one for the readers a commit overtakes, so the bytes are steady once
    that many commits have gone by since the last rebuild (the first
    RF1's `aligned-key-domain`): six operations before the count."""
    import gc

    from tidb_tpu.executor import device_cache
    from tidb_tpu.ops.jax_env import jax
    device_cache.clear()    # (other tests' tables would be evicted midway)
    data = ds.generate(SCALE, SEED)
    eng = _engine(ds, data)
    s = eng.new_session()
    s.vars.update(SETTINGS)
    client = _SessionClient(s)

    def live():
        return sum(a.nbytes for a in jax.live_arrays())

    try:
        assert device_cache.KEPT_GENERATIONS <= 8
        _drive(ds, client, data, 6)
        gc.collect()
        before = live()
        gc.disable()
        try:
            kind = _load("ops", "refresh_pair")
            op = kind.bind({"kind": "refresh_pair", "orders": K,
                            "reads": ["Q1", "Q3", "Q6"]}, ds, None)
            op["next"][0] = 6
            for _ in range(10):
                kind.run(client, op)
            held = live()
        finally:
            gc.enable()
    finally:
        eng.close()
    assert held == before, (before, held)


def test_an_altered_state_is_not_correct(ds):
    data = ds.generate(SCALE, SEED)
    kind = _load("ops", "refresh_pair")
    op = kind.bind({"kind": "refresh_pair", "orders": K,
                    "reads": ["Q1", "Q3", "Q6"]}, ds, None)
    ref = _reference(ds, data)
    state = ref[ds.STATE]
    good = {"n": 0, "rf1_rows": [K, 4 * K],
            "rf2_rows": [state.deleted_rows(0), K],
            "rf1": state.after(0, "rf1"), "rf2": state.after(0, "rf2")}
    assert kind.check(op, good, ref)
    # the state BEFORE the transaction where the one after it is due
    stale = dict(good, rf1=dict(state.base))
    assert not kind.check(op, stale, ref)
    assert not kind.check(op, dict(good, rf2=good["rf1"]), ref)
    assert not kind.check(op, dict(good, rf1_rows=[K, 4 * K - 1]), ref)


@pytest.mark.parametrize("seed", [1, SEED, 2147484201])
def test_the_reference_against_itself(ds, seed):
    """Partial sums added and subtracted = a recomputation over the rows
    that are there; the float64 control differs (Q1, as in every
    `tpch_shaped` cell)."""
    data = ds.generate(0.05, seed)
    state = ds.reference(data)[ds.STATE]
    li, orders = dict(data["lineitem"]), dict(data["orders"])
    keep = np.ones(len(li["l_orderkey"]), dtype=bool)
    for n in range(4):
        rs = ds.refresh_set(data, seed, n)
        li = {c: np.concatenate([v, rs["lineitem"][c]])
              for c, v in li.items()}
        orders = {c: np.concatenate([v, rs["orders"][c]])
                  for c, v in orders.items()}
        keep = np.concatenate([keep, np.ones(600, dtype=bool)])
        for which in ("rf1", "rf2"):
            if which == "rf2":
                a, b = rs["delete"]
                gone = (li["l_orderkey"] >= a) & (li["l_orderkey"] < b)
                assert int((gone & keep).sum()) == state.deleted_rows(n)
                keep &= ~gone
            now = {c: v[keep] for c, v in li.items()}
            # order keys are dense from 0 and new ones follow: an index
            okey = now["l_orderkey"]
            recomputed = ds.rows_of(ds.partial_sums(
                now, orders["o_orderdate"][okey],
                orders["o_orderpriority"][okey]))
            assert state.after(n, which) == recomputed, (n, which)
    assert ds.reference(data) == ds.reference(data)
    # the float64 control: at this size every sum is below 2^53 and a
    # float64 holds it; with prices a thousand times the generator's (the
    # sums of SF=4 and above) it does not, and Q1 differs
    okey = data["lineitem"]["l_orderkey"]
    big = dict(data["lineitem"],
               l_extendedprice=data["lineitem"]["l_extendedprice"] * 1000)
    args = (big, data["orders"]["o_orderdate"][okey],
            data["orders"]["o_orderpriority"][okey])
    assert ds.rows_of(ds.partial_sums(*args, "float64"))["Q1"] != \
        ds.rows_of(ds.partial_sums(*args))["Q1"]
    assert ds.rows_of(ds.partial_sums(*args, "float64"))["Q6"] == \
        ds.rows_of(ds.partial_sums(*args))["Q6"]


def test_a_second_connection_sees_both_tables_or_neither(ds):
    from tidb_tpu.client import Client
    from tidb_tpu.server import Server
    data = ds.generate(SCALE, SEED)
    eng = _engine(ds, data)
    server = Server(eng, port=0).start()
    writer = Client(port=server.port, auto_reconnect=False)
    reader = Client(port=server.port, auto_reconnect=False)
    try:
        for cli in (writer, reader):
            for k, v in SETTINGS.items():
                cli.execute(f"SET {k} = {v!r}" if isinstance(v, str)
                            else f"SET {k} = {v}")
        counts = ("SELECT COUNT(*) FROM orders",
                  "SELECT COUNT(*) FROM lineitem")

        def seen():
            return [int(reader.query(q)[1][0][0]) for q in counts]

        state = ds.reference(data)[ds.STATE]
        before, q3 = seen(), reader.query(ds.STATEMENTS["Q3"])[1]
        assert [tuple(r) for r in q3] == state.base["Q3"]
        sql = ds.refresh_sql(ds.refresh_set(data, SEED, 0, K))["rf1"]
        writer.execute(sql[0])                          # BEGIN
        writer.execute(sql[1])                          # INSERT orders
        assert seen() == before
        writer.execute(sql[2])                          # INSERT lineitem
        assert seen() == before
        assert reader.query(ds.STATEMENTS["Q3"])[1] == q3
        writer.execute(sql[3])                          # COMMIT, acked
        assert seen() == [before[0] + K, before[1] + 4 * K]
        small = ds.RefreshState(data, SEED, k=K)
        assert [tuple(r) for r in reader.query(ds.STATEMENTS["Q3"])[1]] \
            == small.after(0, "rf1")["Q3"]
    finally:
        writer.close()
        reader.close()
        server.stop()
        eng.close()


def test_failpoints_under_the_stream(ds, monkeypatch):
    """`delta-merge-stale`: the read after a transaction falls back to the
    CPU engine, warned, with the reference's rows, and the next read
    extends. `compaction-commit`: the rebuilt generation is abandoned, the
    old one keeps serving the reference's rows, the next drain heals."""
    monkeypatch.setattr(delta, "COMPACT_FILL", 60 / 2048)
    data = ds.generate(SCALE, SEED)
    eng = _engine(ds, data)
    s = eng.new_session()
    s.vars.update(dict(SETTINGS, tidb_tpu_strict="off"))
    client = _SessionClient(s)
    state = ds.RefreshState(data, SEED, k=K)
    q3 = ds.STATEMENTS["Q3"]

    def rows():
        return [tuple(r) for r in client.query(q3)[1]]

    try:
        assert rows() == state.base["Q3"]
        sql = ds.refresh_sql(ds.refresh_set(data, SEED, 0, K))
        for stmt in sql["rf1"]:
            client.execute(stmt)
        failpoint.enable("delta-merge-stale", value="test: stale diff")
        try:
            assert rows() == state.after(0, "rf1")["Q3"]
            assert failpoint.hits("delta-merge-stale") > 0
        finally:
            failpoint.disable("delta-merge-stale")
        ext0 = _counter("tidb_tpu_delta_extensions_total")
        assert rows() == state.after(0, "rf1")["Q3"]
        assert _counter("tidb_tpu_delta_extensions_total") > ext0
        for stmt in sql["rf2"]:
            client.execute(stmt)
        assert rows() == state.after(0, "rf2")["Q3"]
        assert delta.pending_compactions() >= 1
        failpoint.enable("compaction-commit",
                         raise_=RuntimeError("chaos: compaction fault"))
        try:
            assert delta.run_pending_compactions() == 0
        finally:
            failpoint.disable("compaction-commit")
        assert rows() == state.after(0, "rf2")["Q3"]
        for stmt in ds.refresh_sql(ds.refresh_set(data, SEED, 1, K))["rf1"]:
            client.execute(stmt)
        assert rows() == state.after(1, "rf1")["Q3"]
        assert delta.run_pending_compactions() >= 1
        assert rows() == state.after(1, "rf1")["Q3"]
    finally:
        eng.close()
