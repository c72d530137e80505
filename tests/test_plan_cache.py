"""SQL plan cache (ref: planner/core/cache.go): repeated SELECT texts
reuse the compiled physical plan; DDL/ANALYZE/var changes invalidate via
the cache key; plans that baked eager-subquery results never cache."""

import numpy as np
import pytest

from tidb_tpu.session import Engine


@pytest.fixture()
def s():
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE pc (a BIGINT, b BIGINT)")
    s.execute("INSERT INTO pc VALUES " +
              ",".join(f"({i},{i % 7})" for i in range(500)))
    return s


def _hits(s):
    from tidb_tpu.util.observability import REGISTRY
    rows = s.query("SHOW METRICS").rows
    for name, *rest in rows:
        if name == "tidb_tpu_plan_cache_hits_total":
            return float(rest[-1])
    return 0.0


def test_repeated_select_hits_cache(s):
    sql = "SELECT b, COUNT(*), SUM(a) FROM pc GROUP BY b ORDER BY b"
    first = s.query(sql).rows
    h0 = _hits(s)
    second = s.query(sql).rows
    assert second == first
    assert _hits(s) > h0
    assert len(s._plan_cache) >= 1


def test_ddl_invalidates(s):
    sql = "SELECT COUNT(*) FROM pc"
    s.query(sql)
    assert any(k[0] == sql for k in s._plan_cache)
    s.execute("ALTER TABLE pc ADD COLUMN c BIGINT")
    # key embeds the schema version: old entry is unreachable
    s.query(sql)
    versions = {k[1] for k in s._plan_cache if k[0] == sql}
    assert len(versions) == 2


def test_dml_correctness_through_cache(s):
    sql = "SELECT COUNT(*) FROM pc"
    assert s.query(sql).rows == [(500,)]
    s.execute("INSERT INTO pc VALUES (1000, 1)")
    # same plan object, fresh execution: reads the new row
    assert s.query(sql).rows == [(501,)]


def test_eager_subquery_plans_never_cached(s):
    sql = "SELECT COUNT(*) FROM pc WHERE a < (SELECT AVG(a) FROM pc)"
    before = s.query(sql).rows
    assert not any(k[0] == sql for k in s._plan_cache)
    s.execute("INSERT INTO pc VALUES (100000, 1)")   # shifts AVG
    after = s.query(sql).rows
    assert after != before or True    # must recompute, not replay
    # the subquery reran: the new AVG includes the outlier
    avg = s.query("SELECT AVG(a) FROM pc").scalar()
    want = s.query(f"SELECT COUNT(*) FROM pc WHERE a < {avg}").rows
    assert after == want


def test_var_change_misses(s):
    sql = "SELECT SUM(a) FROM pc"
    s.query(sql)
    n0 = len(s._plan_cache)
    s.vars["tidb_tpu_row_threshold"] = 1
    s.query(sql)
    assert len(s._plan_cache) == n0 + 1


class _Untouchable:
    """Stands in for a region's deletion bitmap while a statement is
    planned: whatever reads it raises."""

    def _raise(self, *a, **k):
        raise AssertionError("a deletion bitmap was read while planning")

    __invert__ = __len__ = __iter__ = __array__ = __getitem__ = _raise
    __getattr__ = _raise


def _recounts():
    from tidb_tpu.util.observability import REGISTRY
    return REGISTRY.counters.get(
        ("tidb_tpu_live_rows_recounts_total", ()), 0)


def test_a_plan_cache_hit_reads_no_deletion_bitmap(monkeypatch):
    """The key's live-row counts are stored facts of the snapshot's
    TableData: with every bitmap of every table unreadable for as long
    as `_plan` runs, a warm statement still hits and answers."""
    from tidb_tpu import storage
    from tidb_tpu.session import Session
    monkeypatch.setattr(storage, "REGION_ROWS", 64)
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE pc (a BIGINT, b BIGINT)")
    s.execute("CREATE TABLE pd (b BIGINT, c BIGINT)")
    s.execute("INSERT INTO pc VALUES " +
              ",".join(f"({i},{i % 7})" for i in range(500)))
    s.execute("INSERT INTO pd VALUES " +
              ",".join(f"({i},{i * 10})" for i in range(7)))
    s.execute("DELETE FROM pc WHERE a % 50 = 0")     # tombstones, 8 regions
    sql = ("SELECT pd.c, COUNT(*) FROM pc JOIN pd ON pc.b = pd.b "
           "GROUP BY pd.c ORDER BY pd.c")
    want = s.query(sql).rows
    assert s.query(sql).rows == want and sum(n for _c, n in want) == 490

    real_plan = Session._plan

    def plan_with_bitmaps_unreadable(self, stmt):
        regions = [r for td in eng.store.snapshot()._tables.values()
                   for r in td.regions]
        saved = [r.deleted for r in regions]
        for r in regions:
            object.__setattr__(r, "deleted", _Untouchable())
        try:
            return real_plan(self, stmt)
        finally:
            for r, d in zip(regions, saved):
                object.__setattr__(r, "deleted", d)

    monkeypatch.setattr(Session, "_plan", plan_with_bitmaps_unreadable)
    h0, n0 = _hits(s), _recounts()
    assert s.query(sql).rows == want
    assert s.query(sql).rows == want
    assert _hits(s) == h0 + 2
    assert _recounts() == n0
    # a MISS (the planner's row estimates) reads none either
    s.execute("INSERT INTO pc VALUES (1000, 3)")
    assert sum(n for _c, n in s.query(sql).rows) == 491
    assert _hits(s) == h0 + 2 and _recounts() == n0


def _sizes(s, sql):
    """The live-row part of each cached key of `sql`, oldest first."""
    return [k[3] for k in s._plan_cache if k[0] == sql]


def test_the_key_holds_each_tables_live_rows_as_before(s):
    """Pinned as it was before the counts were stored: the key's sizes
    are (name, int live rows); an INSERT of k rows changes them, misses
    and re-plans; DELETE k then INSERT k gives the old sizes again
    (`stats_version` may move under auto-analyze: compare the sizes)."""
    sql = "SELECT b, COUNT(*) FROM pc GROUP BY b ORDER BY b"
    first = s.query(sql).rows
    assert _sizes(s, sql) == [(("pc", 500),)]
    assert type(_sizes(s, sql)[0][0][1]) is int
    h0 = _hits(s)
    s.execute("INSERT INTO pc VALUES (1000, 0), (1001, 0), (1002, 1)")
    grown = s.query(sql).rows
    assert _hits(s) == h0, "a changed live count must miss and re-plan"
    assert _sizes(s, sql) == [(("pc", 500),), (("pc", 503),)]
    assert grown[0] == (0, first[0][1] + 2) and \
        grown[1] == (1, first[1][1] + 1) and grown[2:] == first[2:]
    s.execute("DELETE FROM pc WHERE a >= 1000")
    assert s.query(sql).rows == first
    s.execute("DELETE FROM pc WHERE a < 3")
    s.execute("INSERT INTO pc VALUES (0, 0), (1, 1), (2, 2)")
    assert s.query(sql).rows == first
    assert set(_sizes(s, sql)) == {(("pc", 500),), (("pc", 503),)}
    # the key last used (a hit moves it to the end) has the old sizes
    last = list(s._plan_cache)[-1]
    assert last[0] == sql and last[3] == (("pc", 500),)
