"""GROUP BY ... WITH ROLLUP: host hash-path oracle semantics (MySQL
super-aggregate rows) and the fused device lowering (levels tiled into
one program per slab with a grouping-level key column) byte-exact
against the host."""

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
    s.execute("CREATE TABLE r (a BIGINT, b BIGINT, c BIGINT, d DOUBLE, "
              "s VARCHAR(8))")
    rng = np.random.default_rng(11)
    rows = []
    for _ in range(4000):
        a = "NULL" if rng.random() < 0.04 else str(int(rng.integers(1, 6)))
        b = "NULL" if rng.random() < 0.04 else str(int(rng.integers(1, 8)))
        c = int(rng.integers(1, 1000))
        d = round(float(rng.uniform(0, 100)), 3)
        sv = ["'ant'", "'bee'", "'cow'", "NULL"][int(rng.integers(0, 4))]
        rows.append(f"({a},{b},{c},{d},{sv})")
    for i in range(0, len(rows), 500):
        s.execute("INSERT INTO r VALUES " + ",".join(rows[i:i + 500]))
    s.execute("CREATE TABLE dim (a BIGINT, name BIGINT)")
    s.execute("INSERT INTO dim VALUES " +
              ",".join(f"({i},{i * 10})" for i in range(1, 6)))
    s.execute("CREATE TABLE mt (a BIGINT, c BIGINT)")  # stays empty
    return s


def run_plan(s, sql):
    plan = s._plan(parse(sql)[0])
    root = build(plan)
    chunks = run_to_completion(root, s._exec_ctx())
    frags = []

    def walk(e):
        if isinstance(e, TpuFragmentExec):
            frags.append(e)
        for ch in getattr(e, "children", []):
            walk(ch)

    walk(root)
    return [r for ch in chunks for r in ch.rows()], frags


def device_vs_host(s, sql, *, max_slab=None, expect_device=True):
    host, _ = run_plan(s, sql)
    s.vars["tidb_tpu_engine"] = "on"
    s.vars["tidb_tpu_row_threshold"] = 1
    if max_slab is not None:
        s.vars["tidb_tpu_max_slab_rows"] = max_slab
    try:
        dev, frags = run_plan(s, sql)
    finally:
        s.vars["tidb_tpu_engine"] = "off"
        s.vars.pop("tidb_tpu_max_slab_rows", None)
    if expect_device:
        assert frags, f"no fragment extracted for: {sql}"
        for f in frags:
            assert f.used_device, \
                f"fell back ({f.fallback_reason}) for: {sql}"
    else:
        assert not any(f.used_device for f in frags), \
            f"expected the host oracle for: {sql}"
    hs, ds = sorted(host, key=repr), sorted(dev, key=repr)
    assert len(hs) == len(ds), (len(hs), len(ds), sql)
    for h, d in zip(hs, ds):
        for x, y in zip(h, d):
            if isinstance(x, float) and y is not None:
                assert abs(x - y) <= 1e-9 * max(1.0, abs(x)), (h, d)
            else:
                assert x == y, (h, d)
    return host


# ---- host oracle semantics (engine off) -----------------------------------

def test_rollup_grand_total_matches_scalar_agg(session):
    rows = session.query("SELECT a, b, COUNT(*), SUM(c) FROM r "
                         "GROUP BY a, b WITH ROLLUP").rows
    total = session.query("SELECT COUNT(*), SUM(c) FROM r").rows[0]
    grand = [r for r in rows if r[0] is None and r[1] is None]
    # genuinely-NULL (a, b) detail rows also have both keys NULL; the
    # grand total is there EXTRA, so: detail(a=NULL,b=NULL) + the
    # subtotal of a=NULL + the grand total itself
    assert any(r[2] == total[0] and r[3] == total[1] for r in grand), \
        (grand, total)


def test_rollup_level_counts(session):
    rows = session.query("SELECT a, b, COUNT(*) FROM r "
                         "GROUP BY a, b WITH ROLLUP").rows
    detail = session.query("SELECT a, b, COUNT(*) FROM r "
                           "GROUP BY a, b").rows
    sub = session.query("SELECT a, COUNT(*) FROM r GROUP BY a").rows
    # one row per (a, b) group, one per a-prefix subtotal, one grand
    assert len(rows) == len(detail) + len(sub) + 1
    n = session.query("SELECT COUNT(*) FROM r").rows[0][0]
    assert sum(r[2] for r in rows) == 3 * n  # every input row counted
    # at each of the 3 levels exactly once


def test_rollup_null_keys_stay_separate_from_subtotals(session):
    rows = session.query("SELECT a, COUNT(*) FROM r "
                         "GROUP BY a WITH ROLLUP").rows
    null_rows = [r for r in rows if r[0] is None]
    null_detail = session.query(
        "SELECT COUNT(*) FROM r WHERE a IS NULL").rows[0][0]
    total = session.query("SELECT COUNT(*) FROM r").rows[0][0]
    # the NULL-keyed detail group and the grand total must be two rows
    assert sorted(r[1] for r in null_rows) == sorted([null_detail, total])


def test_rollup_empty_input_no_rows(session):
    assert session.query("SELECT a, COUNT(*) FROM mt "
                         "GROUP BY a WITH ROLLUP").rows == []


def test_rollup_having_filters_super_aggregates_too(session):
    rows = session.query("SELECT a, b, SUM(c) FROM r "
                         "GROUP BY a, b WITH ROLLUP "
                         "HAVING SUM(c) > 100000").rows
    assert rows
    assert all(r[2] > 100000 for r in rows)


# ---- fused device path vs host oracle -------------------------------------

ROLLUP_QUERIES = [
    "SELECT a, b, COUNT(*), SUM(c), MIN(c), MAX(c) FROM r "
    "GROUP BY a, b WITH ROLLUP",
    "SELECT a, COUNT(*), SUM(c), AVG(c) FROM r GROUP BY a WITH ROLLUP",
    "SELECT s, a, COUNT(*), SUM(d) FROM r GROUP BY s, a WITH ROLLUP",
    "SELECT a, b, COUNT(*), SUM(c) FROM r GROUP BY a, b WITH ROLLUP "
    "ORDER BY a, b, 3 LIMIT 10",
    "SELECT a, b, SUM(c) FROM r GROUP BY a, b WITH ROLLUP "
    "HAVING SUM(c) > 100000",
    "SELECT a, SUM(c) FROM r GROUP BY a WITH ROLLUP ORDER BY a",
]


@pytest.mark.parametrize("sql", ROLLUP_QUERIES)
def test_device_rollup_matches_host(session, sql):
    device_vs_host(session, sql)


def test_device_rollup_multi_slab(session):
    device_vs_host(session, ROLLUP_QUERIES[0], max_slab=1024)


def test_device_rollup_join_tree(session):
    device_vs_host(session,
                   "SELECT dim.name, r.b, COUNT(*), SUM(r.c) FROM r "
                   "JOIN dim ON r.a = dim.a "
                   "GROUP BY dim.name, r.b WITH ROLLUP")


def test_distinct_rollup_stays_on_host_oracle(session):
    # pair columns assume nk key cols; DISTINCT under ROLLUP is gated
    # off the device and must still be correct via the host oracle
    device_vs_host(session,
                   "SELECT a, COUNT(DISTINCT b) FROM r "
                   "GROUP BY a WITH ROLLUP", expect_device=False)


def test_warm_rollup_launch_count(session):
    """A single-fragment rollup is <= slabs + 1 programs at its first
    execution — the level tiling rides inside the per-slab partial
    program, not extra launches — and ONE statement program warm."""
    from tidb_tpu.executor import agg_slabs
    s = session
    sql = ROLLUP_QUERIES[0]
    s.vars["tidb_tpu_engine"] = "on"
    s.vars["tidb_tpu_row_threshold"] = 1
    try:
        agg_slabs._SPEC_CACHE.clear()
        s.query(sql)               # compile + first-touch
        ph = s.last_guard.phases
        # 4000 rows pad into one slab: partial + fused finalize
        assert 1 <= ph.programs_launched <= 2, ph.programs_launched
        s.query(sql)               # warm
        assert s.last_guard.phases.programs_launched == 1
    finally:
        s.vars["tidb_tpu_engine"] = "off"
