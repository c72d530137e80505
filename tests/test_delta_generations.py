"""Generations kept behind the newest: a statement whose snapshot is OLDER
than the device cache's newest generation of a table.

With a writer beside readers that is the ordinary race — a stream takes its
snapshot, the writer's COMMIT is acknowledged, another stream's statement
extends the cache, and the first reaches `device_cache.open_table` one
commit behind — and `AS OF TIMESTAMP` is the same reader, deterministic and
on one connection. The cache serves it from the generation of ITS snapshot
(`CachedTable.kept`), never by a rebuild in place of the newest. Pinned, each
against a plain reference on seeded data:

* a read one commit behind returns that state's exact rows, counted
  `age=kept`, no decline; one three commits behind (the cache keeps ONE
  generation: what a stream beside a writer needs, `PERF.md` PR 40) gets the
  counted rebuild BESIDE; the newest generation stays installed after both;
* Q3 at an older snapshot pairs the `orders` and `lineitem` generations of
  THAT snapshot (the same orders inserted, then deleted, in between);
* two readers and a writer over the wire through the benchmark's operation
  kind (`benchmarks/ops/throughput_streams.py`): every answer is one state
  inside its [lo, hi], no decline moves;
* the bound: the generation beyond `KEPT_GENERATIONS` is freed, its read is
  the counted rebuild BESIDE the cache (in a slot of its own: the plain
  consumers' table stays), and `information_schema.table_storage` returns
  to what it was;
* a cold stream of the operation kind keeps step with the refresher;
* the commit gate's span, and the kind's `check` on altered answers.
"""

import datetime
import gc
import importlib.util
import sys
import threading
import time
import weakref
from pathlib import Path

import numpy as np
import pytest

from tidb_tpu.executor import device_cache as dc
from tidb_tpu.session import Engine
from tidb_tpu.util.observability import REGISTRY

BENCH = Path(__file__).resolve().parents[1] / "benchmarks"
SEED = 2147483659


def _load(kind: str, name: str):
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    spec = importlib.util.spec_from_file_location(
        f"tests_gen_{kind}_{name}", BENCH / kind / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _count(name: str, **labels) -> float:
    want = set(labels.items())
    return sum(v for (n, ls), v in list(REGISTRY.counters.items())
               if n == name and want <= set(ls))


def _reads(age: str) -> float:
    return _count("tidb_tpu_delta_generation_reads_total", age=age)


def _declines() -> float:
    return _count("tidb_tpu_delta_declines_total")


def _now() -> str:
    """A wall-clock instant no commit shares: the store's history is kept
    by wall time, and `AS OF TIMESTAMP` takes the newest version at or
    before it."""
    time.sleep(0.015)
    at = datetime.datetime.fromtimestamp(time.time()).isoformat(sep=" ")
    time.sleep(0.015)
    return at


# ---------------------------------------------------------------------------
# a toy table and its plain reference: a list of rows
# ---------------------------------------------------------------------------

Q = "SELECT a, COUNT(*), SUM(b) FROM t{asof} GROUP BY a ORDER BY a"


def _toy(n=3000):
    rng = np.random.default_rng([SEED, 40])
    rows = [(int(a), int(b)) for a, b in zip(rng.integers(0, 40, n),
                                              rng.integers(0, 5000, n))]
    eng = Engine()
    eng.global_vars["tidb_enable_auto_analyze"] = False
    s = eng.new_session()
    s.execute("CREATE TABLE t (a BIGINT, b BIGINT)")
    s.execute("INSERT INTO t VALUES " + ",".join(
        f"({a}, {b})" for a, b in rows))
    s.vars.update(tidb_tpu_engine="on", tidb_tpu_row_threshold=1,
                  tidb_tpu_compaction="off")
    return eng, s, rows


def _want(rows) -> list:
    """The statement's answer over `rows`, by plain Python."""
    out = {}
    for a, b in rows:
        cnt, total = out.get(a, (0, 0))
        out[a] = (cnt + 1, total + b)
    return [(a, c, t) for a, (c, t) in sorted(out.items())]


def _got(s, asof=None) -> list:
    sql = Q.format(asof=f" AS OF TIMESTAMP '{asof}'" if asof else "")
    return [tuple(int(v) for v in r) for r in s.query(sql).rows]


def _entry(eng, name="t"):
    tid = eng.catalog.info_schema.table(name).id
    for (_dev, sid, t, parts), ent in dc.CACHE.items():
        beside = parts is not None and parts[0] in ("plain", "behind")
        if sid == id(eng.store) and t == tid and not beside:
            return ent
    raise AssertionError(f"table {name} not cached")


def _write(s, rows, step: int) -> None:
    """Commit number `step`: three rows in, and on odd steps one value of
    `a` out — the model `rows` follows."""
    new = [(step % 40, 7000 + step), (39, step), ((step * 7) % 40, 1)]
    s.execute("INSERT INTO t VALUES " + ",".join(
        f"({a}, {b})" for a, b in new))
    rows += new
    if step % 2:
        s.execute(f"DELETE FROM t WHERE a = {step % 40} AND b < 2500")
        rows[:] = [(a, b) for a, b in rows
                   if not (a == step % 40 and b < 2500)]


def test_a_read_behind_the_cache_is_served_from_a_kept_generation():
    eng, s, rows = _toy()
    try:
        states, stamps = [list(rows)], []
        assert _got(s) == _want(rows)
        for step in range(1, 5):        # (the last, even: ONE commit)
            stamps.append(_now())       # a moment in state step - 1
            _write(s, rows, step)
            states.append(list(rows))
            assert _got(s) == _want(rows)       # the cache moves on
        newest = _entry(eng)
        assert newest.is_delta and len(newest.kept) == 1 == dc.KEPT_GENERATIONS
        assert newest.kept[0].delta_version == newest.delta_version - 1
        ext0 = _count("tidb_tpu_delta_extensions_total")
        # stamps[k] lies in state k: one commit behind the newest is state 3
        kept0, rebuilt0, dec0 = _reads("kept"), _reads("rebuilt"), _declines()
        assert _got(s, asof=stamps[3]) == _want(states[3])
        assert _reads("kept") == kept0 + 1
        assert _reads("rebuilt") == rebuilt0 and _declines() == dec0
        assert _entry(eng) is newest, "the newest generation was moved"
        # four commits behind (an odd step is two): older than what is kept
        assert _got(s, asof=stamps[1]) == _want(states[1])
        assert _reads("kept") == kept0 + 1
        assert _reads("rebuilt") == rebuilt0 + 1
        assert _count("tidb_tpu_delta_declines_total", gate="behind") >= 1
        assert _declines() == dec0 + 1
        assert _entry(eng) is newest, "the newest generation was moved"
        # and the newest serves on, without another extension
        new0 = _reads("newest")
        assert _got(s) == _want(rows)
        assert _reads("newest") == new0 + 1
        assert _count("tidb_tpu_delta_extensions_total") == ext0
        # generations of one base build share every base array
        def base_arrays(g):
            return {id(a) for c in g.dev.values()
                    for s, a in c.arrays() if s < g.base_slabs}
        for g in newest.kept:
            assert base_arrays(g) == base_arrays(newest)
    finally:
        eng.close()


def test_commits_between_two_reads_each_leave_their_generation():
    """Two commits went by between two reads of the cache: the read steps
    through the store's history one commit at a time (every extension has
    ONE commit's shapes), so a reader at the snapshot in between finds its
    generation kept."""
    eng, s, rows = _toy()
    try:
        assert _got(s) == _want(rows)
        _write(s, rows, 1)
        assert _got(s) == _want(rows)
        v1 = _entry(eng).delta_version
        _write(s, rows, 2)
        mid, at = list(rows), _now()
        _write(s, rows, 4)
        ext0 = _count("tidb_tpu_delta_extensions_total")
        assert _got(s) == _want(rows)       # 1 → 2 → 4, a commit a step
        newest = _entry(eng)
        assert _count("tidb_tpu_delta_extensions_total") == ext0 + 2
        assert v1 == newest.delta_version - 2
        assert [g.delta_version for g in newest.kept] == [
            newest.delta_version - 1]
        kept0, dec0 = _reads("kept"), _declines()
        assert _got(s, asof=at) == _want(mid)
        assert _reads("kept") == kept0 + 1 and _declines() == dec0
        assert _count("tidb_tpu_delta_extensions_total") == ext0 + 2
        assert _entry(eng) is newest
    finally:
        eng.close()


def test_a_reader_far_behind_costs_the_plain_consumers_nothing():
    """An ORDER BY root over a delta generation reads a plain table cached
    BESIDE it (gate `consumer`). A reader older than what is kept gets a
    plain table too (gate `behind`) — in a slot of its own — and a plain
    consumer at the KEPT snapshot is served alone: neither replaces the
    newest snapshot's plain table, whose next reader rebuilds nothing."""
    eng, s, rows = _toy()
    ordered = "SELECT a, b FROM t{asof} ORDER BY b DESC, a LIMIT 7"

    def top(asof=None):
        sql = ordered.format(asof=f" AS OF TIMESTAMP '{asof}'" if asof else "")
        return [tuple(int(v) for v in r) for r in s.query(sql).rows]

    def want_top(state):
        return sorted(state, key=lambda r: (-r[1], r[0]))[:7]

    def gate(name):
        return _count("tidb_tpu_delta_declines_total", gate=name)

    def slot(tag):
        return {k: e for k, e in dc.CACHE.items() if k[1] == id(eng.store)
                and k[3] is not None and k[3][0] == tag}

    try:
        assert _got(s) == _want(rows)
        old_state, old_at = list(rows), _now()
        _write(s, rows, 2)
        _write(s, rows, 4)
        assert _got(s) == _want(rows)
        kept_state, kept_at = list(rows), _now()
        _write(s, rows, 6)
        assert _got(s) == _want(rows)
        newest = _entry(eng)
        assert newest.kept and want_top(kept_state) != want_top(rows)
        consumer0, behind0 = gate("consumer"), gate("behind")
        assert top() == want_top(rows)
        assert gate("consumer") == consumer0 + 1
        plain = slot("plain")
        assert len(plain) == 1 and not slot("behind")
        # three commits behind: rebuilt beside, in the slot `behind`, which
        # an ORDER BY root at that snapshot reads too
        assert _got(s, asof=old_at) == _want(old_state)
        assert gate("behind") == behind0 + 1 and len(slot("behind")) == 1
        assert top(asof=old_at) == want_top(old_state)
        assert (gate("consumer"), gate("behind")) == \
            (consumer0 + 1, behind0 + 1)
        # an ORDER BY root at the kept snapshot: rebuilt, served alone
        assert top(asof=kept_at) == want_top(kept_state)
        assert gate("consumer") == consumer0 + 2
        assert slot("plain") == plain, "the plain table was replaced"
        # the newest snapshot's readers rebuild nothing
        dec0, rebuilt0 = _declines(), _reads("rebuilt")
        assert top() == want_top(rows)
        assert _got(s) == _want(rows)
        assert _got(s, asof=old_at) == _want(old_state)
        assert _declines() == dec0 and _reads("rebuilt") == rebuilt0
        assert _entry(eng) is newest
    finally:
        eng.close()


def test_readers_meeting_one_commit_extend_once():
    """Several connections reach a commit at once: the first extends, the
    others — who waited for their turn — take what it made."""
    eng, s, rows = _toy()
    sessions = [eng.new_session() for _ in range(4)]
    for x in sessions:
        x.vars.update(s.vars)
    try:
        assert _got(s) == _want(rows)
        for step in (2, 4, 6):
            _write(s, rows, step)
            ext0 = _count("tidb_tpu_delta_extensions_total")
            got, go = [], threading.Barrier(len(sessions))

            def read(x):
                go.wait(10)
                got.append(_got(x))

            threads = [threading.Thread(target=read, args=(x,), daemon=True)
                       for x in sessions]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
            assert not any(t.is_alive() for t in threads)
            assert got == [_want(rows)] * len(sessions)
            assert _count("tidb_tpu_delta_extensions_total") == ext0 + 1
    finally:
        eng.close()


def test_the_bound_frees_the_oldest_and_its_read_is_rebuilt_beside(
        monkeypatch):
    dc.clear()
    eng, s, rows = _toy()

    def stored():
        return sum(int(r[0]) for r in s.query(
            "SELECT PHYSICAL_BYTES FROM information_schema.table_storage "
            "WHERE TABLE_NAME = 't'").rows)

    try:
        assert _got(s) == _want(rows)
        oldest_state, oldest_at = list(rows), _now()
        _write(s, rows, 1)
        assert _got(s) == _want(rows)
        one = stored()      # the base and ONE delta slab
        # (what only this generation holds: its delta slab's liveness
        # mask — the next insert makes the next generation a new one)
        first = weakref.ref(_entry(eng).alive[-1])
        for step in (2, 3, 4):
            _write(s, rows, step)
            assert _got(s) == _want(rows)
        newest = _entry(eng)
        assert len(newest.kept) == dc.KEPT_GENERATIONS == 1
        gc.collect()
        assert first() is None, "the second oldest generation was not freed"
        assert stored() > one       # the kept generation's delta slab
        assert newest.kept_bytes > 0
        assert _count("tidb_tpu_delta_generations_kept") >= 1
        # a snapshot older than every generation kept: the counted plain
        # rebuild beside the cache, the newest stays
        rebuilt0, dec0 = _reads("rebuilt"), _count(
            "tidb_tpu_delta_declines_total", gate="behind")
        assert _got(s, asof=oldest_at) == _want(oldest_state)
        assert _reads("rebuilt") == rebuilt0 + 1
        assert _count("tidb_tpu_delta_declines_total",
                      gate="behind") == dec0 + 1
        assert _entry(eng) is newest
        assert _got(s) == _want(rows)
        assert _entry(eng) is newest
        # no generation kept any more: the accounting is back where one
        # generation's was
        tid = eng.catalog.info_schema.table("t").id
        for key in [k for k in dc.CACHE if k[2] == tid
                    and k[3] is not None and k[3][0] == "behind"]:
            dc._drop_entry(key, dc.CACHE[key])
        monkeypatch.setattr(dc, "KEPT_GENERATIONS", 0)
        _write(s, rows, 6)
        assert _got(s) == _want(rows)
        assert _entry(eng).kept == () and _entry(eng).kept_bytes == 0
        assert stored() == one
        assert _count("tidb_tpu_delta_generations_kept_bytes") == 0
    finally:
        eng.close()


def test_the_commit_gate_is_a_span_when_a_commit_waits(tmp_path):
    """A COMMIT that has to wait for the store's lock records
    `commit.gate` (`wait=lock`, in lane `lock` with every other wait for a
    program lock: `own_lock_wait_ms_per_op` sums that lane) under
    `write.commit`; one that does not records nothing."""
    from tidb_tpu.util import timeline
    eng, s, rows = _toy(200)
    s.execute(f"SET tidb_tpu_trace_dir = '{tmp_path}'")
    try:
        s.execute("INSERT INTO t VALUES (1, 1)")       # uncontended
        s.execute("BEGIN")
        s.execute("INSERT INTO t VALUES (2, 2)")
        held = threading.Event()

        def hold():
            with eng.store._lock:
                held.set()
                time.sleep(0.2)

        t = threading.Thread(target=hold, daemon=True)
        t.start()
        assert held.wait(10)
        s.execute("COMMIT")                             # waits for `hold`
        t.join(10)
        assert not t.is_alive()
    finally:
        timeline.stop_global()
        eng.close()
    gates = [e for e in timeline.last_events()
             if e.get("name") == "commit.gate"]
    assert len(gates) == 1, gates
    assert gates[0]["cat"] == "lock"
    assert gates[0]["args"]["wait"] == "lock"
    assert gates[0]["dur"] > 50_000


# ---------------------------------------------------------------------------
# the refresh data: Q3 joins two tables every transaction writes
# ---------------------------------------------------------------------------

SCALE, K = 0.01, 15
SETTINGS = dict(tidb_tpu_engine="on", tidb_tpu_row_threshold=1,
                tidb_tpu_strict="on", tidb_tpu_max_slab_rows=16384,
                tidb_tpu_compaction="off")


@pytest.fixture(scope="module")
def ds():
    return _load("datasets", "tpch_throughput")


def _refresh_engine(ds, data):
    eng = Engine()
    eng.global_vars["tidb_enable_auto_analyze"] = False
    ds.rf._bulk_load(eng, data)
    return eng


def _rows(s, sql) -> list:
    return [tuple(str(v) for v in r) for r in s.execute(sql)[0].rows]


def test_q3_at_an_older_snapshot_pairs_that_snapshots_generations(ds):
    """RF1 0 inserts orders with their lineitems; the SAME orders and
    lineitems are deleted again. Q3 AS OF the state in between must see
    both tables' rows of the transaction: lineitem's generation of that
    snapshot with orders' newest would lose the new lineitems' join
    partners, the reverse would count orders' rows twice over nothing."""
    data = ds.generate(SCALE, SEED)
    eng = _refresh_engine(ds, data)
    s = eng.new_session()
    s.vars.update(SETTINGS)
    state = ds.RefreshState(data, SEED, k=K)
    q3 = ds.STATEMENTS["Q3"]

    def as_of(at):
        # (one AS OF a statement: it pins the statement's whole snapshot)
        assert "FROM lineitem JOIN" in q3
        return q3.replace("FROM lineitem JOIN",
                          f"FROM lineitem AS OF TIMESTAMP '{at}' JOIN")

    try:
        want0 = [tuple(r) for r in state.base["Q3"]]
        want1 = [tuple(r) for r in state.after(0, "rf1")["Q3"]]
        assert want0 != want1
        rs = ds.refresh_set(data, SEED, 0, K)
        lo, hi = int(rs["orders"]["o_orderkey"][0]), \
            int(rs["orders"]["o_orderkey"][-1]) + 1

        def cycle():
            """RF1 0's rows in, then out again: two commits, read after
            each → a moment between them."""
            for stmt in ds.refresh_sql(rs)["rf1"]:
                s.execute(stmt)
            assert _rows(s, q3) == want1
            between = _now()
            for stmt in ("BEGIN",
                         f"DELETE FROM lineitem WHERE l_orderkey >= {lo} "
                         f"AND l_orderkey < {hi}",
                         f"DELETE FROM orders WHERE o_orderkey >= {lo} "
                         f"AND o_orderkey < {hi}", "COMMIT"):
                s.execute(stmt)
            assert _rows(s, q3) == want0
            return between

        assert _rows(s, q3) == want0
        at1 = cycle()
        newest = {t: _entry(eng, t) for t in ("orders", "lineitem")}
        dec0, kept0 = _declines(), _reads("kept")
        assert _rows(s, as_of(at1)) == want1
        assert _reads("kept") >= kept0 + 2      # both tables' generations
        assert _rows(s, q3) == want0
        assert _declines() == dec0
        assert {t: _entry(eng, t) for t in newest} == newest
        # three commits behind, at a snapshot that is no base build's: both
        # tables rebuilt beside, a join structure built for this statement
        # alone — the newest snapshot's structure stays for its next reader
        far = cycle()
        cycle()
        newest = {t: _entry(eng, t) for t in ("orders", "lineitem")}
        aligned = {k: e for k, e in dc._ALIGNED.items()
                   if k[0] == id(eng.store)}
        assert aligned
        assert _rows(s, as_of(far)) == want1
        assert {k: dc._ALIGNED.get(k) for k in aligned} == aligned
        dec1 = _declines()
        assert _rows(s, q3) == want0
        assert _declines() == dec1
        assert {t: _entry(eng, t) for t in newest} == newest
    finally:
        eng.close()


def _kind(ds, streams: int):
    kind = _load("ops", "throughput_streams")
    # (the tests send one connection's operations after another's: a cold
    # stream's wait for the refresher would be for nobody)
    kind.PACE_TIMEOUT_S = 0.0
    op = kind.bind({"kind": "throughput_streams", "streams": streams,
                    "orders": K, "reads": ["Q1", "Q3", "Q6"]}, ds, None)
    return kind, op


def _reference(ds, data):
    ref = ds.reference(data)
    ref[ds.STATE] = ds.RefreshState(data, SEED, k=K)
    return ref


def test_two_readers_and_a_writer_every_answer_is_one_state(ds):
    from tidb_tpu.client import Client
    from tidb_tpu.server import Server
    data = ds.generate(SCALE, SEED)
    eng = _refresh_engine(ds, data)
    server = Server(eng, port=0).start()
    kind, op = _kind(ds, streams=2)
    clients = []
    for _ in range(3):
        cli = Client(port=server.port, auto_reconnect=False, timeout=100)
        for k, v in SETTINGS.items():
            cli.execute(f"SET {k} = {v!r}" if isinstance(v, str)
                        else f"SET {k} = {v}")
        clients.append(cli)
    errors = []

    def loop(cli, n, pace=0):
        """`n` operations; a paced client (the writer) sends its next once
        the streams have answered `pace` more statements, so that its
        commits are spread over the readers' whole run."""
        try:
            for i in range(n):
                limit = time.monotonic() + 30
                while pace and time.monotonic() < limit and sum(
                        len(h) for h in op["history"].values()) \
                        < warm + i * (pace + 1):
                    time.sleep(0.002)
                kind.run(cli, op)
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(repr(e))

    try:
        # first sight pins the roles: the streams, then the refresher;
        # every statement and one refresh pair run once before the race
        kind.run(clients[0], op)
        for _ in range(3):
            kind.run(clients[1], op)
        for _ in range(2):
            kind.run(clients[2], op)
        for cli in clients[:2]:
            for _ in range(3):
                kind.run(cli, op)
        dec0 = _declines()
        warm = sum(len(h) for h in op["history"].values())
        threads = [threading.Thread(target=loop, args=a, daemon=True)
                   for a in zip(clients, (60, 60, 20), (0, 0, 5))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(100)
        assert not any(t.is_alive() for t in threads), "a client hangs"
        assert not errors, errors
        assert _declines() == dec0
    finally:
        for cli in clients:
            cli.close()
        server.stop()
        eng.close()
    ref = _reference(ds, data)
    answers = [a for h in op["history"].values() for a in h]
    assert len(answers) == 1 + 3 + 2 + 6 + 140
    wrong = [a for a in answers if not kind.check(op, a, ref)]
    assert not wrong, wrong[:2]
    assert op["acked"][0] == 22
    seen = {s for a in answers for r in a.get("reads", ())
            for s in kind.states_of(op, ref[ds.STATE], r)}
    assert len(seen) >= 8, "the readers never saw the tables move"


def test_the_comparison_refuses_what_isolation_forbids(ds):
    """The kind's `check` on answers made from the reference itself: a
    state below lo, above hi, a mix of two states' rows, and a state that
    goes backwards on one connection."""
    data = ds.generate(SCALE, SEED)
    kind, op = _kind(ds, streams=1)
    ref = _reference(ds, data)
    state = ref[ds.STATE]

    def judged(reads) -> list:
        """One connection's answers, one read each, judged in order."""
        op["history"] = {0: [
            {"role": "stream", "conn": 0, "seq": i, "reads": [
                {"q": q, "rows": rows, "lo": lo, "hi": hi}]}
            for i, (q, rows, lo, hi) in enumerate(reads)]}
        op["verdicts"] = None
        return [kind.check(op, a, ref) for a in op["history"][0]]

    at = lambda s, q: ds.at(state, s)[q]  # noqa: E731
    assert at(2, "Q3") != at(3, "Q3") != at(4, "Q3")
    assert judged([("Q3", at(3, "Q3"), 2, 4)]) == [True]
    assert judged([("Q3", at(3, "Q3"), 3, 3)]) == [True]
    assert judged([("Q3", at(1, "Q3"), 2, 4)]) == [False]      # below lo
    assert judged([("Q3", at(5, "Q3"), 2, 4)]) == [False]      # above hi
    # lineitem's rows of state 3 beside orders' of state 2 are no state's:
    # here, two groups of state 3's answer beside three of state 2's
    mixed = list(at(3, "Q3")[:2]) + list(at(2, "Q3")[2:])
    assert mixed not in (list(at(2, "Q3")), list(at(3, "Q3")))
    assert judged([("Q3", mixed, 2, 4)]) == [False]
    # backwards on one connection, although each lies inside its bounds
    assert judged([("Q1", at(4, "Q1"), 2, 4),
                   ("Q1", at(3, "Q1"), 2, 4)]) == [True, False]
    assert judged([("Q1", at(3, "Q1"), 2, 4),
                   ("Q1", at(4, "Q1"), 2, 4)]) == [True, True]
    # a transaction's row counts
    op["history"] = {1: [{"role": "refresh", "conn": 1, "seq": 0,
                          "txn": {"t": 0, "counts": [K, 4 * K]}},
                         {"role": "refresh", "conn": 1, "seq": 1,
                          "txn": {"t": 1, "counts": [
                              state.deleted_rows(0) - 1, K]}}]}
    op["verdicts"] = None
    assert [kind.check(op, a, ref) for a in op["history"][1]] == \
        [True, False]


def test_a_cold_stream_keeps_step_with_the_refresher(ds):
    """Until each of its reads has run four times a stream's statement i is
    sent only once transaction i is acknowledged: twelve statements beside
    twelve transactions, then it never waits again; a stream the refresher
    is ahead of does not wait; and none waits for a refresher that is
    gone."""
    kind = _load("ops", "throughput_streams")
    spec = {"kind": "throughput_streams", "streams": 1, "orders": K,
            "reads": ["Q1", "Q3", "Q6"]}
    op = kind.bind(spec, ds, None)

    class Stream:
        def query(self, sql):
            return None, []

    cli, done = Stream(), []

    def stream():
        kind.run(cli, op)                       # first sight: every read
        for _ in range(13):
            kind.run(cli, op)
            done.append(op["acked"][0])

    t = threading.Thread(target=stream, daemon=True)
    t.start()
    for n in range(1, 13):
        limit = time.monotonic() + 10
        while len(done) < n - 1 and time.monotonic() < limit:
            time.sleep(0.001)
        time.sleep(0.01)
        assert len(done) == n - 1, "a cold stream ran ahead of the refresher"
        op["acked"][0] = n                      # transaction n - 1 is through
    t.join(10)
    assert not t.is_alive(), "a warm stream waited"
    assert done == list(range(1, 13)) + [12]
    assert op["roles"][id(cli)]["cold"] is None
    # the refresher five transactions ahead: five statements at once
    op = kind.bind(spec, ds, None)
    op["acked"][0] = 5
    began = time.monotonic()
    for _ in range(1 + 5):
        kind.run(cli, op)
    assert time.monotonic() - began < 5 and op["roles"][id(cli)]["cold"]
    # a refresher that never acknowledges anything: one bounded wait
    kind.PACE_TIMEOUT_S = 0.05
    op = kind.bind(spec, ds, None)
    began = time.monotonic()
    for _ in range(4):
        kind.run(cli, op)
    assert 0.05 <= time.monotonic() - began < 5
    assert op["roles"][id(cli)]["cold"] is None
