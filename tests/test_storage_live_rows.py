"""A region knows how many of its rows live (storage.Region.live_rows,
TableData.live_rows): the number is handed over by every write path of
`Store` and must equal a fresh count of the deletion bitmap after each
of them; `gc_stats` and `stats()` read it."""

import numpy as np
import pytest

from tidb_tpu import storage
from tidb_tpu.chunk import Chunk, Column
from tidb_tpu.errors import TxnError
from tidb_tpu.session import Engine
from tidb_tpu.storage import Region, TableData
from tidb_tpu.types import bigint
from tidb_tpu.util.observability import REGISTRY

RECOUNTS = ("tidb_tpu_live_rows_recounts_total", ())
ROWS = 64           # REGION_ROWS for these tests: several regions a table


def _recounts():
    return REGISTRY.counters.get(RECOUNTS, 0)


def _values(lo, hi):
    return ",".join(f"({i},{i % 5})" for i in range(lo, hi))


def _check(eng):
    """Every carried count equals the bitmap counted afresh."""
    snap = eng.store.snapshot()
    stats = eng.store.stats()
    assert set(stats) == set(snap._tables)
    for tid, td in snap._tables.items():
        fresh = [int((~r.deleted).sum()) for r in td.regions]
        assert [r.live_rows for r in td.regions] == fresh
        assert td.live_rows == sum(fresh)
        total = sum(r.num_rows for r in td.regions)
        assert eng.store.gc_stats(tid) == (sum(fresh), total - sum(fresh),
                                           len(td.regions))
        assert stats[tid] == (len(td.regions), sum(fresh))


def _tid(eng, name):
    return eng.catalog.info_schema.table(name).id


def _live(eng, name):
    return eng.store.snapshot().table_data(_tid(eng, name)).live_rows


def _regions(eng, name):
    return eng.store.snapshot().table_data(_tid(eng, name)).regions


# ---- the write paths, one scenario each ---------------------------------
def _append_fresh(eng, s):
    s.execute("INSERT INTO t VALUES " + _values(200, 200 + 3 * ROWS))
    assert _live(eng, "t") == 100 + 3 * ROWS
    return 100 + 3 * ROWS


def _append_top_off(eng, s):
    before = len(_regions(eng, "t"))
    s.execute("INSERT INTO t VALUES " + _values(200, 210))   # 36 + 10 ≤ 64
    assert len(_regions(eng, "t")) == before
    assert _regions(eng, "t")[-1].live_rows == 46
    return 110


def _delete_autocommit(eng, s):
    s.execute("DELETE FROM t WHERE a < 30")
    # a second delete over a region that already has tombstones
    s.execute("DELETE FROM t WHERE a < 35 OR a = 70")
    return 64


def _delete_committed_txn(eng, s):
    s.execute("BEGIN")
    s.execute("DELETE FROM t WHERE b = 0")          # 20 rows
    s.execute("INSERT INTO t VALUES (500, 1), (501, 2)")
    _check(eng)
    assert _live(eng, "t") == 100                   # nothing applied yet
    s.execute("COMMIT")
    return 82


def _delete_rolled_back_txn(eng, s):
    s.execute("BEGIN")
    s.execute("DELETE FROM t WHERE b = 0")
    s.execute("INSERT INTO t VALUES (500, 1)")
    s.execute("ROLLBACK")
    return 100


def _delete_compacts(eng, s):
    ids = {r.id for r in _regions(eng, "t")}
    s.execute("DELETE FROM t WHERE a >= 20 AND a < 64")   # below the ratio
    assert eng.store.gc_stats(_tid(eng, "t"))[1] == 44
    s.execute("DELETE FROM t WHERE a < 10")   # 54 of 100 dead: compacts
    live, dead, _n = eng.store.gc_stats(_tid(eng, "t"))
    assert (live, dead) == (46, 0)
    # the first region was rewritten, the untouched one kept as it was
    now = _regions(eng, "t")
    assert [r.id in ids for r in now] == [False, True]
    assert [r.live_rows for r in now] == [10, 36]
    return 46


def _update(eng, s):
    s.execute("UPDATE t SET b = b + 10 WHERE a % 2 = 0")
    assert s.query("SELECT COUNT(*) FROM t WHERE b >= 10").rows == [(50,)]
    return 100


def _truncate(eng, s):
    s.execute("DELETE FROM t WHERE a < 5")
    s.execute("TRUNCATE TABLE t")
    assert _regions(eng, "t") == ()
    s.execute("INSERT INTO t VALUES (1, 1)")
    return 1


def _conflicting_double_delete(eng, s):
    s2 = eng.new_session()
    s.execute("BEGIN")
    s2.execute("BEGIN")
    s.execute("DELETE FROM t WHERE a < 10")
    s2.execute("DELETE FROM t WHERE a >= 5 AND a < 15")
    s.execute("COMMIT")
    _check(eng)
    with pytest.raises(TxnError):
        s2.execute("COMMIT")        # first committer wins: nothing applied
    return 90


def _drop_partition_remap(eng, s):
    s.execute("CREATE TABLE p (a BIGINT, b BIGINT) PARTITION BY RANGE (a) ("
              "PARTITION p0 VALUES LESS THAN (100), "
              "PARTITION p1 VALUES LESS THAN (200), "
              "PARTITION p2 VALUES LESS THAN (300))")
    s.execute("INSERT INTO p VALUES " + _values(0, 300))
    s.execute("DELETE FROM p WHERE a % 10 = 0")         # 10 a partition
    # a delete keeps the region's partition tag, so the partition's
    # rows go with it and later ordinals shift down with their counts
    assert {r.part for r in _regions(eng, "p")} == {0, 1, 2}
    s.execute("ALTER TABLE p DROP PARTITION p0")
    assert {r.part for r in _regions(eng, "p")} == {0, 1}
    assert s.query("SELECT COUNT(*), MIN(a) FROM p").rows == [(180, 101)]
    assert _live(eng, "p") == 180
    assert s.query(
        "SELECT PARTITION_NAME, TABLE_ROWS FROM "
        "information_schema.partitions WHERE TABLE_NAME = 'p' "
        "ORDER BY PARTITION_NAME").rows == [("p1", 90), ("p2", 90)]
    return 100


def _truncate_partition_after_delete(eng, s):
    s.execute("CREATE TABLE p (a BIGINT, b BIGINT) PARTITION BY RANGE (a) ("
              "PARTITION p0 VALUES LESS THAN (100), "
              "PARTITION p1 VALUES LESS THAN (200))")
    s.execute("INSERT INTO p VALUES " + _values(0, 200))
    s.execute("DELETE FROM p WHERE a = 1")
    s.execute("ALTER TABLE p TRUNCATE PARTITION p0")
    assert s.query("SELECT COUNT(*), MIN(a) FROM p").rows == [(100, 100)]
    assert _live(eng, "p") == 100
    return 100


SCENARIOS = [_append_fresh, _append_top_off, _delete_autocommit,
             _delete_committed_txn, _delete_rolled_back_txn,
             _delete_compacts, _update, _truncate,
             _conflicting_double_delete, _drop_partition_remap,
             _truncate_partition_after_delete]


@pytest.mark.parametrize("scenario", SCENARIOS,
                         ids=[f.__name__.lstrip("_") for f in SCENARIOS])
def test_carried_counts_equal_a_fresh_count(scenario, monkeypatch):
    monkeypatch.setattr(storage, "REGION_ROWS", ROWS)
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE t (a BIGINT, b BIGINT)")
    s.execute("INSERT INTO t VALUES " + _values(0, 100))   # 64 + 36
    assert [r.live_rows for r in _regions(eng, "t")] == [64, 36]
    _check(eng)
    n0 = _recounts()
    want = scenario(eng, s)
    _check(eng)
    assert _live(eng, "t") == want
    assert s.query("SELECT COUNT(*) FROM t").rows == [(want,)]
    assert s.query(
        "SELECT TABLE_ROWS FROM information_schema.tables "
        "WHERE TABLE_NAME = 't'").rows == [(want,)]
    # every write path handed its regions the number: none was recounted
    assert _recounts() == n0


def _chunk(n):
    return Chunk([Column(bigint(), np.arange(n, dtype=np.int64))])


def test_a_region_built_without_its_count_counts_once():
    deleted = np.zeros(10, dtype=bool)
    deleted[[2, 5, 7]] = True
    n0 = _recounts()
    r = Region(1, _chunk(10), deleted)
    assert r.live_rows == 7
    assert r.live_rows == 7 and _recounts() == n0 + 1   # kept, not redone
    given = Region(2, _chunk(10), deleted, None, 7)
    assert given.live_rows == 7 and _recounts() == n0 + 1
    td = TableData((r, given))
    assert td.live_rows == 14 and _recounts() == n0 + 1
    assert TableData(()).live_rows == 0


def test_store_level_writes_carry_their_counts(monkeypatch):
    """The Store API below SQL (tools and loaders call it directly)."""
    monkeypatch.setattr(storage, "REGION_ROWS", ROWS)
    store = storage.Store()
    store.create_table(7)
    n0 = _recounts()
    store.append(7, _chunk(150))
    regions = store.snapshot().table_data(7).regions
    assert [r.live_rows for r in regions] == [64, 64, 22]
    mask = np.zeros(64, dtype=bool)
    mask[:40] = True
    assert store.delete(7, {regions[0].id: mask}) == 40
    # deleting the same rows again deletes nothing and keeps the count
    assert store.delete(7, {regions[0].id: mask}) == 0
    assert store.gc_stats(7) == (110, 40, 3)
    assert store.stats()[7] == (3, 110)
    # a mask shorter than the region (staged before a top-off) is padded
    short = np.ones(10, dtype=bool)
    assert store.delete(7, {regions[2].id: short}) == 10
    td = store.snapshot().table_data(7)
    assert [r.live_rows for r in td.regions] == [24, 64, 12]
    assert td.live_rows == 100 and _recounts() == n0
