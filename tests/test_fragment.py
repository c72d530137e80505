"""Device fragment execution vs CPU oracle (the vec-vs-scalar twin-test
pattern of the reference, SURVEY §4 tier 1: builtin_*_vec_test.go asserts
vec(X) == scalar(X); here device fragment == CPU volcano pipeline)."""

import numpy as np
import pytest

from tidb_tpu.executor import run_to_completion

from tidb_tpu.executor.builder import build
from tidb_tpu.executor.fragment import TpuFragmentExec
from tidb_tpu.parser import parse
from tidb_tpu.session import Engine


@pytest.fixture(scope="module")
def session():
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE t (a BIGINT, b DOUBLE, c VARCHAR(10), "
              "d DECIMAL(10,2), e DATE)")
    rng = np.random.default_rng(7)
    rows = []
    for _ in range(6000):
        a = int(rng.integers(0, 9))
        b = float(rng.normal())
        c = ["ant", "bee", "cow", "dog"][int(rng.integers(0, 4))]
        d = round(float(rng.uniform(0, 500)), 2)
        e = f"2021-{int(rng.integers(1, 13)):02d}-{int(rng.integers(1, 28)):02d}"
        rows.append(f"({a},{b},'{c}',{d},'{e}')")
    rows.append("(NULL,NULL,NULL,NULL,NULL)")
    rows.append("(3,NULL,'ant',NULL,NULL)")
    s.execute("INSERT INTO t VALUES " + ",".join(rows))
    return s


def run_device(s, sql, *, max_slab=None):
    s.vars["tidb_tpu_engine"] = "on"
    s.vars["tidb_tpu_row_threshold"] = 1
    if max_slab is not None:
        s.vars["tidb_tpu_max_slab_rows"] = max_slab
    else:
        s.vars.pop("tidb_tpu_max_slab_rows", None)
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
        assert frags, f"no fragment extracted for: {sql}"
        for f in frags:
            assert f.used_device, f"fell back to CPU for: {sql}"
        return [r for ch in chunks for r in ch.rows()]
    finally:
        s.vars["tidb_tpu_engine"] = "off"
        s.vars.pop("tidb_tpu_max_slab_rows", None)


def assert_same(rows1, rows2, ordered=False):
    assert len(rows1) == len(rows2)
    if not ordered:
        rows1 = sorted(rows1, key=str)
        rows2 = sorted(rows2, key=str)
    for r1, r2 in zip(rows1, rows2):
        for v1, v2 in zip(r1, r2):
            if isinstance(v1, float) and v2 is not None:
                assert abs(v1 - v2) <= 1e-5 * max(1.0, abs(v2)), (r1, r2)
            else:
                assert v1 == v2, (r1, r2)


QUERIES = [
    "SELECT c, a, COUNT(*), SUM(d), AVG(b), MIN(b), MAX(a) FROM t "
    "WHERE a < 6 GROUP BY c, a",
    "SELECT COUNT(*), SUM(a), MIN(b), MAX(d), AVG(d) FROM t WHERE c = 'ant'",
    "SELECT a, COUNT(*), COUNT(b), SUM(b) FROM t GROUP BY a",
    "SELECT e, COUNT(*) FROM t GROUP BY e",
    "SELECT c, VAR_POP(b), STDDEV(b) FROM t GROUP BY c",
    "SELECT a, SUM(d * 2 + 1) FROM t WHERE b > 0 GROUP BY a",
]


@pytest.mark.parametrize("sql", QUERIES)
def test_agg_fragment_matches_cpu(session, sql):
    dev = run_device(session, sql)
    cpu = session.query(sql).rows
    assert_same(dev, cpu)


@pytest.mark.parametrize("sql", QUERIES[:2])
def test_multi_slab_merge(session, sql):
    dev = run_device(session, sql, max_slab=1024)
    cpu = session.query(sql).rows
    assert_same(dev, cpu)


def test_topn_fragment(session):
    sql = "SELECT a, b, c FROM t ORDER BY b DESC LIMIT 9"
    assert_same(run_device(session, sql), session.query(sql).rows,
                ordered=True)


def test_topn_nulls_first_asc(session):
    sql = "SELECT c, a FROM t ORDER BY c, a LIMIT 5"
    dev = run_device(session, sql)
    cpu = session.query(sql).rows
    assert_same(dev, cpu, ordered=True)
    assert dev[0][0] is None  # NULLs first under ASC


def test_topn_multi_slab(session):
    sql = "SELECT a, d FROM t ORDER BY d DESC, a LIMIT 11"
    dev = run_device(session, sql, max_slab=1024)
    assert_same(dev, session.query(sql).rows, ordered=True)


def test_filter_fragment(session):
    sql = "SELECT a, b, c FROM t WHERE b > 1.2 AND a >= 4"
    assert_same(run_device(session, sql), session.query(sql).rows)


def test_filter_fragment_strings(session):
    sql = "SELECT c, d FROM t WHERE c >= 'bee' AND d < 100"
    assert_same(run_device(session, sql), session.query(sql).rows)


def test_sort_fragment(session):
    sql = "SELECT a, b FROM t WHERE a IS NOT NULL ORDER BY a, b DESC"
    assert_same(run_device(session, sql), session.query(sql).rows,
                ordered=True)


def test_group_cap_overflow_retry(session):
    # d has ~6000 distinct values; default cap 65536 covers it, but force a
    # tiny starting cap to exercise the retry loop
    session.vars["tidb_tpu_group_cap"] = 64
    try:
        sql = "SELECT d, COUNT(*) FROM t GROUP BY d"
        assert_same(run_device(session, sql), session.query(sql).rows)
    finally:
        session.vars.pop("tidb_tpu_group_cap", None)


def test_small_input_stays_on_cpu(session):
    session.vars["tidb_tpu_engine"] = "on"
    session.vars["tidb_tpu_row_threshold"] = 10 ** 9
    try:
        plan = session._plan(parse("SELECT a, COUNT(*) FROM t GROUP BY a")[0])
        names = []

        def walk(p):
            names.append(type(p).__name__)
            for c in p.children:
                walk(c)

        walk(plan)
        assert "PhysTpuFragment" not in names
    finally:
        session.vars["tidb_tpu_engine"] = "off"
        session.vars["tidb_tpu_row_threshold"] = 1


def test_multi_slab_per_slab_cap_overflow(session):
    # Advisor r1 high-severity repro: per-slab distinct groups exceed the
    # cap while the MERGED group count stays under it — the per-slab
    # n_groups check must trigger retry, not silently conflate groups.
    # Group by the DOUBLE column: floats have no cached bounds, so this
    # exercises the sort-factorize path (perfect-hash grouping would route
    # around the clipping bug this guards).
    session.vars["tidb_tpu_group_cap"] = 64
    try:
        sql = "SELECT b, COUNT(*) FROM t WHERE b IS NOT NULL GROUP BY b"
        dev = run_device(session, sql, max_slab=2048)
        assert_same(dev, session.query(sql).rows)
    finally:
        session.vars.pop("tidb_tpu_group_cap", None)


def test_device_table_cache_reuse_and_invalidation():
    from tidb_tpu.executor import device_cache
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE ct (a BIGINT, c VARCHAR(8))")
    s.execute("INSERT INTO ct VALUES " + ",".join(
        f"({i % 7}, 'v{i % 3}')" for i in range(4000)))
    sql = "SELECT a, COUNT(*) FROM ct GROUP BY a"
    # serial single-session workload → deterministically device 0
    key = (0, id(eng.store), eng.catalog.info_schema.table("ct").id, None)
    r1 = run_device(s, sql)
    ent1 = device_cache.CACHE.get(key)
    assert ent1 is not None and 0 in ent1.dev
    r2 = run_device(s, sql)
    ent2 = device_cache.CACHE.get(key)
    assert ent2 is ent1          # cache hit: same device payload object
    assert_same(r1, r2)
    # a write replaces TableData → identity check must rebuild
    s.execute("INSERT INTO ct VALUES (99, 'new')")
    r3 = run_device(s, sql)
    ent3 = device_cache.CACHE.get(key)
    assert ent3 is not ent1
    assert sum(r[1] for r in r3) == 4001
    assert_same(r3, s.query(sql).rows)


def test_txn_reads_bypass_device_cache():
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE tx (a BIGINT)")
    s.execute("INSERT INTO tx VALUES " + ",".join(
        f"({i % 5})" for i in range(3000)))
    s.execute("BEGIN")
    s.execute("INSERT INTO tx VALUES (77)")
    sql = "SELECT a, COUNT(*) FROM tx GROUP BY a"
    dev = run_device(s, sql)      # staged row must be visible
    assert any(r[0] == 77 for r in dev)
    s.execute("ROLLBACK")
    dev2 = run_device(s, sql)
    assert not any(r[0] == 77 for r in dev2)


def test_strict_mode_and_fallback_reason():
    from tidb_tpu.errors import ExecutionError
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE st (a BIGINT)")  # empty table → FragmentFallback
    s.vars["tidb_tpu_engine"] = "on"
    s.vars["tidb_tpu_row_threshold"] = 0
    s.vars["tidb_tpu_strict"] = True
    try:
        with pytest.raises(ExecutionError, match="fell back"):
            s.query("SELECT a, COUNT(*) FROM st GROUP BY a")
        s.vars["tidb_tpu_strict"] = False
        rs = s.query("SELECT a, COUNT(*) FROM st GROUP BY a")
        assert rs.rows == []
    finally:
        s.vars["tidb_tpu_engine"] = "off"


# ---- fallback-reason taxonomy (tidb_tpu_device_fallbacks_total) -----------

def test_source_reason_codes_stay_in_taxonomy():
    """Every reason= literal across the fragment layers is a member of
    FALLBACK_REASONS — the metric label vocabulary never drifts."""
    import os
    import re

    from tidb_tpu.executor.eligibility import FALLBACK_REASONS
    base = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tidb_tpu", "executor")
    found = 0
    for mod in ("fragment.py", "dist_fragment.py", "tree_fragment.py",
                "device_emit.py", "window.py"):
        with open(os.path.join(base, mod)) as f:
            src = f.read()
        for code in re.findall(r'reason="([a-z-]+)"', src):
            assert code in FALLBACK_REASONS, (mod, code)
            found += 1
    assert found >= 10  # the taxonomy is actually in use


def test_unknown_reason_normalizes_to_shape():
    from tidb_tpu.executor.eligibility import FragmentFallback
    assert FragmentFallback("x", reason="no-such-code").reason == "shape"
    assert FragmentFallback("x").reason == "shape"


def test_empty_input_fallback_explain_matches_metric():
    """EXPLAIN ANALYZE's device:fallback(code) and the reason= label on
    tidb_tpu_device_fallbacks_total carry the SAME stable code."""
    from tidb_tpu.util.observability import REGISTRY
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE empt (a BIGINT, b DOUBLE)")
    key = ("tidb_tpu_device_fallbacks_total",
           (("reason", "empty-input"),))
    before = REGISTRY.counters.get(key, 0)
    s.vars["tidb_tpu_engine"] = "on"
    s.vars["tidb_tpu_row_threshold"] = 1
    try:
        rows = s.query("EXPLAIN ANALYZE SELECT a, COUNT(*), SUM(b) "
                       "FROM empt GROUP BY a").rows
    finally:
        s.vars["tidb_tpu_engine"] = "off"
    txt = "\n".join(str(r) for r in rows)
    assert "device:fallback(empty-input)" in txt, txt
    assert REGISTRY.counters.get(key, 0) == before + 1


@pytest.mark.parametrize("sql", [
    # DISTINCT under ROLLUP: pair columns assume nk key cols
    "SELECT a, COUNT(DISTINCT c) FROM t GROUP BY a WITH ROLLUP",
    # computed string in an IN-list: no per-dictionary codeset to prepare
    "SELECT COUNT(*) FROM t WHERE SUBSTRING(c, 1, 2) IN ('an', 'be')",
])
def test_ineligible_shape_classes_never_extract_a_fragment(session, sql):
    """Planning-time gates (taxonomy class `shape`) keep the whole plan
    on the host — no fragment, no device attempt, stable results."""
    s = session
    s.vars["tidb_tpu_engine"] = "on"
    s.vars["tidb_tpu_row_threshold"] = 1
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
        assert not frags, f"shape-gated query extracted a fragment: {sql}"
        dev = [r for ch in chunks for r in ch.rows()]
    finally:
        s.vars["tidb_tpu_engine"] = "off"
    assert sorted(dev, key=str) == sorted(s.query(sql).rows, key=str)
