"""Memory tracker + disk spill (ref: util/memory/tracker.go,
util/chunk/row_container.go, executor/aggregate.go AggSpillDiskAction)."""

import numpy as np
import pytest

from tidb_tpu.errors import MemoryQuotaExceeded
from tidb_tpu.session import Engine
from tidb_tpu.util.memory import (PartitionedChunkSpill, Tracker,
                                  hash_partition)


def test_tracker_quota_and_handler():
    root = Tracker("q", quota=100)
    child = root.child("op")
    child.consume(60)
    assert root.consumed == 60 and child.consumed == 60
    fired = []

    def handler():
        fired.append(True)
        child.release(60)   # shed everything
        return True

    child.add_handler(handler)
    child.consume(80)       # 140 > 100 → handler sheds
    assert fired
    child.release(80)
    child.remove_handler(handler)
    with pytest.raises(MemoryQuotaExceeded):
        child.consume(200)


def test_hash_partition_null_and_negzero():
    keys = [(np.array([1.0, -0.0, 0.0, 5.5]),
             np.array([True, True, True, False]))]
    p = hash_partition(keys, 8)
    assert p[1] == p[2]      # -0.0 and 0.0 co-locate
    assert p[3] == p[3]      # NULL lands deterministically


def test_chunk_spill_roundtrip():
    from tidb_tpu import types as T
    from tidb_tpu.chunk import Chunk, Column
    fts = [T.bigint(), T.varchar()]
    sp = PartitionedChunkSpill(4, fts)
    c = Chunk([Column(fts[0], np.arange(10, dtype=np.int64), None),
               Column(fts[1], np.array([f"s{i}" for i in range(10)],
                                       dtype=object), None)])
    sp.add_partitioned(c, np.arange(10) % 4)
    total = 0
    for p in range(4):
        for ch in sp.read(p):
            total += ch.num_rows
            assert ch.columns[1].values[0].startswith("s")
    assert total == 10
    sp.close()


@pytest.fixture(scope="module")
def session():
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE big (k BIGINT, g BIGINT, s VARCHAR(8), "
              "x DOUBLE)")
    s.execute("CREATE TABLE dim (k BIGINT, name VARCHAR(8), "
              "PRIMARY KEY (k))")
    rng = np.random.default_rng(77)
    rows = []
    for i in range(40000):
        k = int(rng.integers(0, 9000))
        g = int(rng.integers(0, 3000))
        rows.append(f"({k},{g},'v{g % 11}',{round(float(rng.uniform(0, 9)), 3)})")
    s.execute("INSERT INTO big VALUES " + ",".join(rows))
    s.execute("INSERT INTO dim VALUES " +
              ",".join(f"({i},'n{i % 5}')" for i in range(8000)))
    s.execute("ANALYZE TABLE big")
    s.vars["max_chunk_size"] = 1024
    return s


SPILL_QUERIES = [
    "SELECT g, COUNT(*), SUM(x), COUNT(DISTINCT s) FROM big GROUP BY g",
    "SELECT name, COUNT(*), SUM(x) FROM big JOIN dim ON big.k = dim.k "
    "GROUP BY name",
    "SELECT COUNT(*) FROM big LEFT JOIN dim ON big.k = dim.k "
    "WHERE name IS NULL",
    "SELECT COUNT(*) FROM big WHERE k IN (SELECT k FROM dim WHERE k < 500)",
]


@pytest.mark.parametrize("sql", SPILL_QUERIES)
def test_spill_matches_in_memory(session, sql):
    s = session
    s.vars.pop("tidb_mem_quota_query", None)
    base = sorted(map(tuple, s.query(sql).rows), key=str)
    s.vars["tidb_mem_quota_query"] = 400_000
    try:
        spl = sorted(map(tuple, s.query(sql).rows), key=str)
    finally:
        s.vars.pop("tidb_mem_quota_query", None)
    assert len(base) == len(spl)
    for a, b in zip(base, spl):
        for x, y in zip(a, b):
            if isinstance(x, float):
                assert abs(x - y) <= 1e-6 * max(1.0, abs(x)), (a, b)
            else:
                assert x == y, (a, b)


def test_unspillable_query_cancels(session):
    s = session
    s.vars["tidb_mem_quota_query"] = 20_000
    try:
        with pytest.raises(MemoryQuotaExceeded):
            # cross join (no equi keys) cannot grace-partition
            s.query("SELECT COUNT(*) FROM big b1, big b2 "
                    "WHERE b1.x + b2.x > 100")
    finally:
        s.vars.pop("tidb_mem_quota_query", None)


def test_multi_slab_device_sort(session):
    # a full ORDER BY (no LIMIT → Sort root, not TopN) over small slabs:
    # device per-slab sort + host run merge must equal the CPU sort
    from tidb_tpu.executor import run_to_completion
    from tidb_tpu.executor.builder import build
    from tidb_tpu.executor.fragment import TpuFragmentExec
    from tidb_tpu.parser import parse
    s = session
    sql = "SELECT k, g, x FROM big ORDER BY x DESC, k, g"
    base = s.query(sql).rows
    s.vars.update(tidb_tpu_engine="on", tidb_tpu_row_threshold=1,
                  tidb_tpu_max_slab_rows=4096, tidb_tpu_strict="on")
    try:
        plan = s._plan(parse(sql)[0])
        root = build(plan)
        chunks = run_to_completion(root, s._exec_ctx())
        frags = []

        def walk(e):
            if isinstance(e, TpuFragmentExec):
                frags.append(e)
            for c in getattr(e, "children", []):
                walk(c)

        walk(root)
        assert frags and all(f.used_device for f in frags), \
            [f.fallback_reason for f in frags]
        dev = [r for ch in chunks for r in ch.rows()]
    finally:
        for k in ("tidb_tpu_engine", "tidb_tpu_row_threshold",
                  "tidb_tpu_max_slab_rows", "tidb_tpu_strict"):
            s.vars.pop(k, None)
    assert len(dev) == len(base)
    for a, b in zip(base, dev):
        assert a[0] == b[0] and a[1] == b[1], (a, b)


# ---- failpoints + GC -------------------------------------------------------

def test_failpoint_commit_error():
    from tidb_tpu.errors import TxnError
    from tidb_tpu.util import failpoint
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE fp (a BIGINT)")
    with failpoint.enabled("store-commit", raise_=TxnError("injected")):
        with pytest.raises(TxnError):
            s.execute("INSERT INTO fp VALUES (1)")
        assert failpoint.hits("store-commit") == 1
    # recovered after disable
    s.execute("INSERT INTO fp VALUES (2)")
    assert s.query("SELECT COUNT(*) FROM fp").rows == [(1,)]


def test_failpoint_device_fallback():
    from tidb_tpu.util import failpoint
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE fd (a BIGINT)")
    s.execute("INSERT INTO fd VALUES " +
              ",".join(f"({i})" for i in range(5000)))
    s.vars.update(tidb_tpu_engine="on", tidb_tpu_row_threshold=1)
    with failpoint.enabled("device-fragment",
                           raise_=RuntimeError("injected device loss")):
        # device dies → CPU fallback still answers correctly
        assert s.query("SELECT SUM(a) FROM fd").rows == [(12497500,)]
        assert failpoint.hits("device-fragment") >= 1


def test_gc_compaction_reclaims_tombstones():
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE gc (a BIGINT)")
    s.execute("INSERT INTO gc VALUES " +
              ",".join(f"({i})" for i in range(10000)))
    info = eng.catalog.info_schema.table("gc")
    s.execute("DELETE FROM gc WHERE a < 8000")   # 80% dead → compaction
    live, dead, regions = eng.store.gc_stats(info.id)
    assert dead == 0, "tombstones not reclaimed"
    assert live == 2000
    assert s.query("SELECT COUNT(*), MIN(a) FROM gc").rows == [(2000, 8000)]
    # caches keyed by TableData identity see the rewrite
    s.execute("INSERT INTO gc VALUES (1)")
    assert s.query("SELECT COUNT(*) FROM gc WHERE a = 1").rows == [(1,)]


def test_parallel_partial_workers_match_sequential():
    # the hash-agg partial-worker pipeline (tidb_tpu_cpu_concurrency > 1)
    # must be byte-identical to sequential, incl. order-sensitive
    # first_row states and DISTINCT dedup
    import numpy as np
    from tidb_tpu.session import Engine
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE pw (g BIGINT, v BIGINT, t VARCHAR(4))")
    rng = np.random.default_rng(2)
    s.execute("INSERT INTO pw VALUES " + ",".join(
        f"({int(rng.integers(0, 50))},{int(rng.integers(0, 1000))},"
        f"'t{int(rng.integers(0, 3))}')" for i in range(30000)))
    s.vars["max_chunk_size"] = 1024      # many batches
    sql = ("SELECT g, COUNT(*), SUM(v), COUNT(DISTINCT v), MIN(t) "
           "FROM pw GROUP BY g ORDER BY g")
    s.vars["tidb_tpu_cpu_concurrency"] = 1
    seq = s.query(sql).rows
    s.vars["tidb_tpu_cpu_concurrency"] = 8
    par = s.query(sql).rows
    assert par == seq
