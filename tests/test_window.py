"""Window functions vs a row-at-a-time python oracle
(ref: executor/window.go semantics; default RANGE frame with ties)."""

import numpy as np
import pytest

from tidb_tpu.session import Engine


@pytest.fixture(scope="module")
def session():
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE w (id BIGINT, g VARCHAR(4), o BIGINT, "
              "x DOUBLE, d DECIMAL(8,2))")
    rng = np.random.default_rng(17)
    rows = []
    for i in range(800):
        g = "NULL" if rng.random() < 0.05 else \
            f"'g{int(rng.integers(0, 6))}'"
        o = "NULL" if rng.random() < 0.05 else str(int(rng.integers(0, 20)))
        x = round(float(rng.normal(0, 10)), 3)
        d = round(float(rng.uniform(0, 50)), 2)
        rows.append(f"({i},{g},{o},{x},{d})")
    s.execute("INSERT INTO w VALUES " + ",".join(rows))
    return s


def fetch(session, sql):
    return session.query(sql).rows


def _partitions(rows, gi):
    parts = {}
    for r in rows:
        parts.setdefault(r[gi], []).append(r)
    return parts


def _okey(o):
    # MySQL ASC NULLS FIRST total order for the oracle
    return (0, 0) if o is None else (1, o)


def test_row_number_rank_dense(session):
    rows = fetch(session,
                 "SELECT id, g, o, "
                 "ROW_NUMBER() OVER (PARTITION BY g ORDER BY o), "
                 "RANK() OVER (PARTITION BY g ORDER BY o), "
                 "DENSE_RANK() OVER (PARTITION BY g ORDER BY o) FROM w")
    for part in _partitions(rows, 1).values():
        part.sort(key=lambda r: _okey(r[2]))
        seen_orders = []
        rank_of = {}
        for i, r in enumerate(part):
            if r[2] not in rank_of:
                rank_of[r[2]] = i + 1
                seen_orders.append(r[2])
        rns = sorted(r[3] for r in part)
        assert rns == list(range(1, len(part) + 1))
        for r in part:
            assert r[4] == rank_of[r[2]], r
            assert r[5] == seen_orders.index(r[2]) + 1, r


def test_full_partition_aggregates(session):
    rows = fetch(session,
                 "SELECT g, x, SUM(x) OVER (PARTITION BY g), "
                 "COUNT(*) OVER (PARTITION BY g), "
                 "MIN(x) OVER (PARTITION BY g), "
                 "MAX(x) OVER (PARTITION BY g), "
                 "AVG(d) OVER (PARTITION BY g) FROM w")
    for part in _partitions(rows, 0).values():
        xs = [r[1] for r in part]
        for r in part:
            assert r[2] == pytest.approx(sum(xs), rel=1e-9)
            assert r[3] == len(part)
            assert r[4] == pytest.approx(min(xs))
            assert r[5] == pytest.approx(max(xs))


def test_running_sum_with_ties(session):
    rows = fetch(session,
                 "SELECT g, o, x, SUM(x) OVER (PARTITION BY g ORDER BY o) "
                 "FROM w")
    for part in _partitions(rows, 0).values():
        part.sort(key=lambda r: _okey(r[1]))
        for r in part:
            # RANGE frame: all rows with o <= current o (peers included)
            expect = sum(p[2] for p in part
                         if _okey(p[1]) <= _okey(r[1]))
            assert r[3] == pytest.approx(expect, rel=1e-9), (r, expect)


def test_lag_lead(session):
    rows = fetch(session,
                 "SELECT id, g, o, x, "
                 "LAG(x) OVER (PARTITION BY g ORDER BY o, id), "
                 "LEAD(x, 2, 0.5) OVER (PARTITION BY g ORDER BY o, id) "
                 "FROM w")
    for part in _partitions(rows, 1).values():
        part.sort(key=lambda r: (_okey(r[2]), r[0]))
        for i, r in enumerate(part):
            expect_lag = part[i - 1][3] if i >= 1 else None
            assert r[4] == (pytest.approx(expect_lag)
                            if expect_lag is not None else None), r
            expect_lead = part[i + 2][3] if i + 2 < len(part) else 0.5
            assert r[5] == pytest.approx(expect_lead), r


def test_running_min_max(session):
    rows = fetch(session,
                 "SELECT g, o, x, MIN(x) OVER (PARTITION BY g ORDER BY o), "
                 "MAX(x) OVER (PARTITION BY g ORDER BY o) FROM w")
    for part in _partitions(rows, 0).values():
        part.sort(key=lambda r: _okey(r[1]))
        for r in part:
            frame = [p[2] for p in part if _okey(p[1]) <= _okey(r[1])]
            assert r[3] == pytest.approx(min(frame)), r
            assert r[4] == pytest.approx(max(frame)), r


def test_window_desc_order(session):
    rows = fetch(session,
                 "SELECT g, o, ROW_NUMBER() OVER "
                 "(PARTITION BY g ORDER BY o DESC) FROM w "
                 "WHERE o IS NOT NULL")
    for part in _partitions(rows, 0).values():
        part.sort(key=lambda r: -r[1])
        by_rn = sorted(part, key=lambda r: r[2])
        os = [r[1] for r in by_rn]
        assert os == sorted(os, reverse=True)


def test_no_partition(session):
    rows = fetch(session, "SELECT id, ROW_NUMBER() OVER (ORDER BY id) "
                          "FROM w")
    rows.sort(key=lambda r: r[0])
    for i, r in enumerate(rows):
        assert r[1] == i + 1


def test_window_with_arithmetic_and_alias(session):
    rows = fetch(session,
                 "SELECT g, RANK() OVER (PARTITION BY g ORDER BY o) + 100 "
                 "AS r100 FROM w")
    assert all(r[1] >= 101 for r in rows)


def test_window_in_where_rejected(session):
    from tidb_tpu.errors import TiDBTPUError
    with pytest.raises(TiDBTPUError):
        session.query("SELECT id FROM w "
                      "WHERE ROW_NUMBER() OVER (ORDER BY id) < 5")


def test_empty_input(session):
    rows = fetch(session, "SELECT g, ROW_NUMBER() OVER (ORDER BY o) "
                          "FROM w WHERE id < 0")
    assert rows == []


# ---- device differential (fragment engine window root) ---------------------

DEVICE_WINDOW_QUERIES = [
    "SELECT g, o, id, ROW_NUMBER() OVER (PARTITION BY g ORDER BY o, id) "
    "FROM w",
    "SELECT g, o, RANK() OVER (PARTITION BY g ORDER BY o), "
    "DENSE_RANK() OVER (PARTITION BY g ORDER BY o) FROM w",
    "SELECT g, SUM(x) OVER (PARTITION BY g), "
    "COUNT(*) OVER (PARTITION BY g), MIN(x) OVER (PARTITION BY g) FROM w",
    "SELECT g, o, SUM(x) OVER (PARTITION BY g ORDER BY o) FROM w",
    "SELECT g, o, MIN(x) OVER (PARTITION BY g ORDER BY o), "
    "MAX(x) OVER (PARTITION BY g ORDER BY o) FROM w",
    "SELECT g, o, id, LAG(x) OVER (PARTITION BY g ORDER BY o, id), "
    "LEAD(x, 2, 0.25) OVER (PARTITION BY g ORDER BY o, id) FROM w",
]


@pytest.mark.parametrize("sql", DEVICE_WINDOW_QUERIES)
def test_device_window_matches_cpu(session, sql):
    from tidb_tpu.executor import run_to_completion
    from tidb_tpu.executor.builder import build
    from tidb_tpu.executor.fragment import TpuFragmentExec
    from tidb_tpu.parser import parse
    s = session
    cpu = s.query(sql).rows
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
        assert frags and all(f.used_device for f in frags), \
            [f.fallback_reason for f in frags]
        dev = [r for ch in chunks for r in ch.rows()]
    finally:
        s.vars["tidb_tpu_engine"] = "off"
    assert len(dev) == len(cpu)
    for a, b in zip(sorted(cpu, key=str), sorted(dev, key=str)):
        for x, y in zip(a, b):
            if isinstance(x, float) and y is not None:
                assert abs(x - y) <= 1e-4 * max(1.0, abs(x)), (a, b)
            else:
                assert x == y, (a, b)


# ---- frame clauses (ROWS BETWEEN …) ----------------------------------------

def _frame_oracle(rows, key, val, pre, post, agg):
    """Brute-force ROWS-frame oracle over (partition_key, value) rows."""
    from collections import defaultdict
    parts = defaultdict(list)
    for i, (k, v) in enumerate(rows):
        parts[k].append((i, v))
    out = {}
    for k, items in parts.items():
        for j, (i, _v) in enumerate(items):
            lo = 0 if pre is None else max(j - pre, 0)
            hi = len(items) - 1 if post is None else min(j + post,
                                                         len(items) - 1)
            window = [v for _, v in items[lo:hi + 1] if v is not None]
            if agg == "sum":
                out[i] = sum(window) if window else None
            elif agg == "count":
                out[i] = len(window)
            elif agg == "min":
                out[i] = min(window) if window else None
            elif agg == "max":
                out[i] = max(window) if window else None
    return out


def test_rows_frame_sum_count_min_max():
    import numpy as np
    from tidb_tpu.session import Engine
    s = Engine().new_session()
    s.execute("CREATE TABLE wf (id BIGINT, k BIGINT, v BIGINT)")
    rng = np.random.default_rng(31)
    data = []
    for i in range(400):
        k = int(rng.integers(0, 5))
        v = None if rng.random() < 0.1 else int(rng.integers(0, 100))
        data.append((k, v))
    s.execute("INSERT INTO wf VALUES " + ",".join(
        f"({i},{k},{v if v is not None else 'NULL'})"
        for i, (k, v) in enumerate(data)))
    for agg, pre, post, clause in [
        ("sum", 2, 0, "ROWS BETWEEN 2 PRECEDING AND CURRENT ROW"),
        ("sum", 1, 3, "ROWS BETWEEN 1 PRECEDING AND 3 FOLLOWING"),
        ("count", None, 0, "ROWS UNBOUNDED PRECEDING"),
        ("min", 3, 3, "ROWS BETWEEN 3 PRECEDING AND 3 FOLLOWING"),
        ("max", 0, None,
         "ROWS BETWEEN CURRENT ROW AND UNBOUNDED FOLLOWING"),
        ("min", None, 2, "ROWS BETWEEN UNBOUNDED PRECEDING AND "
                         "2 FOLLOWING"),
    ]:
        got = dict(s.query(
            f"SELECT id, {agg.upper()}(v) OVER "
            f"(PARTITION BY k ORDER BY id {clause}) FROM wf").rows)
        want = _frame_oracle(data, "k", "v", pre, post, agg)
        assert got == want, (agg, clause)


def test_first_last_value():
    from tidb_tpu.session import Engine
    s = Engine().new_session()
    s.execute("CREATE TABLE fv (id BIGINT, k BIGINT, v BIGINT)")
    s.execute("INSERT INTO fv VALUES (1,1,10),(2,1,20),(3,1,20),(4,1,30),"
              "(5,2,7)")
    rows = s.query(
        "SELECT id, FIRST_VALUE(v) OVER (PARTITION BY k ORDER BY v), "
        "LAST_VALUE(v) OVER (PARTITION BY k ORDER BY v) FROM fv "
        "ORDER BY id").rows
    # default frame: last_value ends at the current PEER group (MySQL)
    assert rows == [(1, 10, 10), (2, 10, 20), (3, 10, 20), (4, 10, 30),
                    (5, 7, 7)]
    rows = s.query(
        "SELECT id, LAST_VALUE(v) OVER (PARTITION BY k ORDER BY v "
        "ROWS BETWEEN UNBOUNDED PRECEDING AND UNBOUNDED FOLLOWING) "
        "FROM fv ORDER BY id").rows
    assert rows == [(1, 30), (2, 30), (3, 30), (4, 30), (5, 7)]


def test_frames_on_device():
    import numpy as np
    from tidb_tpu.session import Engine
    from tidb_tpu.executor import run_to_completion
    from tidb_tpu.executor.builder import build
    from tidb_tpu.executor.fragment import TpuFragmentExec
    from tidb_tpu.parser import parse
    s = Engine().new_session()
    s.execute("CREATE TABLE wd (id BIGINT, k BIGINT, v BIGINT)")
    rng = np.random.default_rng(13)
    s.execute("INSERT INTO wd VALUES " + ",".join(
        f"({i},{int(rng.integers(0, 7))},{int(rng.integers(0, 50))})"
        for i in range(3000)))
    sql = ("SELECT id, SUM(v) OVER (PARTITION BY k ORDER BY id "
           "ROWS BETWEEN 2 PRECEDING AND 1 FOLLOWING), "
           "MIN(v) OVER (PARTITION BY k ORDER BY id "
           "ROWS BETWEEN 3 PRECEDING AND CURRENT ROW) FROM wd")
    cpu = sorted(map(str, s.query(sql).rows))
    s.vars.update({"tidb_tpu_engine": "on", "tidb_tpu_row_threshold": 1,
                   "tidb_tpu_strict": "on"})
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
        dev = sorted(map(str, (r for ch in chunks for r in ch.rows())))
    finally:
        s.vars.update({"tidb_tpu_engine": "off", "tidb_tpu_strict": "off"})
    assert dev == cpu


def test_frame_edge_cases():
    from tidb_tpu.session import Engine
    import pytest as _pt
    s = Engine().new_session()
    s.execute("CREATE TABLE wfe (id BIGINT, v BIGINT)")
    s.execute("INSERT INTO wfe VALUES (1,10),(2,20),(3,30),(4,40)")
    # fully-FOLLOWING frames run off the partition end: empty -> NULL
    rows = s.query(
        "SELECT id, SUM(v) OVER (ORDER BY id ROWS BETWEEN 2 FOLLOWING "
        "AND 3 FOLLOWING), MIN(v) OVER (ORDER BY id ROWS BETWEEN "
        "2 FOLLOWING AND 3 FOLLOWING) FROM wfe ORDER BY id").rows
    # row 2's window [idx 3, idx 4] clamps to just idx 3; rows 3/4 run
    # entirely off the end → empty frame → NULL
    assert rows == [(1, 70, 30), (2, 40, 40), (3, None, None),
                    (4, None, None)]
    # invalid bounds are clean errors, not crashes
    with _pt.raises(Exception, match="UNBOUNDED FOLLOWING"):
        s.query("SELECT SUM(v) OVER (ORDER BY id ROWS BETWEEN "
                "UNBOUNDED FOLLOWING AND CURRENT ROW) FROM wfe")
    with _pt.raises(Exception, match="shorthand|PRECEDING"):
        s.query("SELECT SUM(v) OVER (ORDER BY id ROWS 2 FOLLOWING) "
                "FROM wfe")
    with _pt.raises(Exception, match="parameter count"):
        s.query("SELECT FIRST_VALUE(v, id) OVER (ORDER BY id) FROM wfe")


def test_rank_family_extras():
    from tidb_tpu.session import Engine
    s = Engine().new_session()
    s.execute("CREATE TABLE wr (id BIGINT, k BIGINT, v BIGINT)")
    s.execute("INSERT INTO wr VALUES (1,1,10),(2,1,20),(3,1,20),(4,1,40),"
              "(5,2,5),(6,2,6),(7,2,7)")
    rows = s.query(
        "SELECT id, PERCENT_RANK() OVER (PARTITION BY k ORDER BY v), "
        "CUME_DIST() OVER (PARTITION BY k ORDER BY v), "
        "NTILE(2) OVER (PARTITION BY k ORDER BY v), "
        "NTH_VALUE(v, 2) OVER (PARTITION BY k ORDER BY v) "
        "FROM wr ORDER BY id").rows
    # partition k=1: ranks 1,2,2,4 over 4 rows
    assert rows[0][1:] == (0.0, 0.25, 1, None)       # nth frame ends at peer
    assert rows[1][1] == pytest.approx(1 / 3)
    assert rows[1][2] == pytest.approx(0.75)
    assert rows[1][3] == 1 and rows[1][4] == 20
    assert rows[2][1] == pytest.approx(1 / 3)
    assert rows[2][3] == 2 and rows[2][4] == 20
    assert rows[3][1:] == (1.0, 1.0, 2, 20)
    # partition k=2: 3 rows, NTILE(2) → buckets 1,1,2
    assert [r[3] for r in rows[4:]] == [1, 1, 2]
    # NTH_VALUE with an explicit full frame sees the whole partition
    rows = s.query(
        "SELECT id, NTH_VALUE(v, 3) OVER (PARTITION BY k ORDER BY v "
        "ROWS BETWEEN UNBOUNDED PRECEDING AND UNBOUNDED FOLLOWING) "
        "FROM wr ORDER BY id").rows
    assert [r[1] for r in rows] == [20, 20, 20, 20, 7, 7, 7]


# ---- RANGE frames with offsets ---------------------------------------------

def _range_oracle(rows, pre, post, agg, desc=False):
    """Positional oracle over (part, okey, val) rows, MySQL RANGE
    semantics: sort each partition by key (NULLs first ASC / last DESC);
    a NULL row's offset bound is its NULL-block edge; unbounded sides
    reach the partition edges (and thus include NULL-key rows); non-NULL
    offset bounds never include NULLs."""
    from collections import defaultdict
    parts = defaultdict(list)
    for i, (p, k, v) in enumerate(rows):
        parts[p].append((i, k, v))
    out = {}
    for items in parts.values():
        items = sorted(items, key=lambda t: (
            (t[1] is None) == desc, (-t[1] if desc else t[1])
            if t[1] is not None else 0))
        n = len(items)
        null_pos = [j for j, (_i, k, _v) in enumerate(items)
                    if k is None]
        for j, (i, k, _v) in enumerate(items):
            if k is None:
                lo = 0 if pre is None else null_pos[0]
                hi = n - 1 if post is None else null_pos[-1]
            else:
                def inside(kk):
                    lo_ok = pre is None or (
                        kk >= k - pre if not desc else kk <= k + pre)
                    hi_ok = post is None or (
                        kk <= k + post if not desc else kk >= k - post)
                    return lo_ok and hi_ok
                ok_pos = [jj for jj, (_x, kk, _y) in enumerate(items)
                          if kk is not None and inside(kk)]
                lo = 0 if pre is None else (min(ok_pos) if ok_pos
                                            else n)
                hi = n - 1 if post is None else (max(ok_pos) if ok_pos
                                                 else -1)
            window = [v for _x, _k, v in items[lo:hi + 1]
                      if v is not None]
            if agg == "sum":
                out[i] = sum(window) if window else None
            elif agg == "count":
                out[i] = len(window)
    return out


def _mk_range_table(s, name, with_nulls=True):
    import numpy as np
    rng = np.random.default_rng(41)
    data = []
    for _ in range(500):
        p = int(rng.integers(0, 4))
        k = None if (with_nulls and rng.random() < 0.08) \
            else int(rng.integers(0, 40))
        v = None if rng.random() < 0.1 else int(rng.integers(0, 100))
        data.append((p, k, v))
    s.execute(f"CREATE TABLE {name} (id BIGINT, p BIGINT, k BIGINT, "
              f"v BIGINT)")
    s.execute(f"INSERT INTO {name} VALUES " + ",".join(
        f"({i},{p},{'NULL' if k is None else k},"
        f"{'NULL' if v is None else v})"
        for i, (p, k, v) in enumerate(data)))
    return data


def test_range_frame_sum_count():
    from tidb_tpu.session import Engine
    s = Engine().new_session()
    data = _mk_range_table(s, "rf")
    for agg, pre, post, clause in [
        ("sum", 3, 0, "RANGE BETWEEN 3 PRECEDING AND CURRENT ROW"),
        ("sum", 2, 5, "RANGE BETWEEN 2 PRECEDING AND 5 FOLLOWING"),
        ("count", 0, 0, "RANGE BETWEEN CURRENT ROW AND CURRENT ROW"),
        ("sum", None, 1,
         "RANGE BETWEEN UNBOUNDED PRECEDING AND 1 FOLLOWING"),
        ("count", 4, None,
         "RANGE BETWEEN 4 PRECEDING AND UNBOUNDED FOLLOWING"),
        ("sum", 3, 3, "RANGE 3 PRECEDING"),     # shorthand: end=current…
    ]:
        if clause.endswith("3 PRECEDING") and "BETWEEN" not in clause:
            post = 0
        got = dict(s.query(
            f"SELECT id, {agg.upper()}(v) OVER "
            f"(PARTITION BY p ORDER BY k {clause}) FROM rf").rows)
        want = _range_oracle(data, pre, post, agg)
        assert got == want, (agg, clause,
                             {i: (got[i], want[i]) for i in got
                              if got[i] != want[i]})


def test_range_frame_desc():
    from tidb_tpu.session import Engine
    s = Engine().new_session()
    data = _mk_range_table(s, "rfd")
    got = dict(s.query(
        "SELECT id, SUM(v) OVER (PARTITION BY p ORDER BY k DESC "
        "RANGE BETWEEN 3 PRECEDING AND CURRENT ROW) FROM rfd").rows)
    want = _range_oracle(data, 3, 0, "sum", desc=True)
    assert got == want


def test_range_frame_first_last_value():
    from tidb_tpu.session import Engine
    s = Engine().new_session()
    s.execute("CREATE TABLE rfv (id BIGINT, k BIGINT, v BIGINT)")
    s.execute("INSERT INTO rfv VALUES (1,1,10),(2,2,20),(3,4,40),"
              "(4,5,50),(5,9,90)")
    rows = s.query(
        "SELECT id, FIRST_VALUE(v) OVER (ORDER BY k "
        "RANGE BETWEEN 2 PRECEDING AND 1 FOLLOWING), "
        "LAST_VALUE(v) OVER (ORDER BY k "
        "RANGE BETWEEN 2 PRECEDING AND 1 FOLLOWING) FROM rfv "
        "ORDER BY id").rows
    # frames: k=1→{1,2}; k=2→{1,2}; k=4→{2,4,5}; k=5→{4,5}; k=9→{9}
    assert rows == [(1, 10, 20), (2, 10, 20), (3, 20, 50),
                    (4, 40, 50), (5, 90, 90)]


def test_range_frame_decimal_key_scaled_offsets():
    from tidb_tpu.session import Engine
    s = Engine().new_session()
    s.execute("CREATE TABLE rdc (id BIGINT, k DECIMAL(8,2), v BIGINT)")
    s.execute("INSERT INTO rdc VALUES (1,'1.00',1),(2,'1.75',2),"
              "(3,'2.00',4),(4,'3.50',8),(5,'9.00',16)")
    got = dict(s.query(
        "SELECT id, SUM(v) OVER (ORDER BY k RANGE BETWEEN 1 PRECEDING "
        "AND CURRENT ROW) FROM rdc").rows)
    # offsets scale into DECIMAL units: 1 ⇒ 1.00
    assert got == {1: 1, 2: 3, 3: 7, 4: 8, 5: 16}


def test_range_frame_errors():
    import pytest
    from tidb_tpu.errors import PlanError
    from tidb_tpu.session import Engine
    s = Engine().new_session()
    s.execute("CREATE TABLE rfe (id BIGINT, a BIGINT, b VARCHAR(4), "
              "v BIGINT)")
    s.execute("INSERT INTO rfe VALUES (1,1,'x',1)")
    with pytest.raises(PlanError, match="exactly one ORDER BY"):
        s.query("SELECT SUM(v) OVER (ORDER BY id, a RANGE BETWEEN 1 "
                "PRECEDING AND CURRENT ROW) FROM rfe")
    with pytest.raises(PlanError, match="numeric or temporal"):
        s.query("SELECT SUM(v) OVER (ORDER BY b RANGE BETWEEN 1 "
                "PRECEDING AND CURRENT ROW) FROM rfe")
    with pytest.raises(PlanError, match="ROWS frame"):
        s.query("SELECT MIN(v) OVER (ORDER BY a RANGE BETWEEN 1 "
                "PRECEDING AND CURRENT ROW) FROM rfe")


def test_range_frame_device_matches_cpu():
    from tidb_tpu.session import Engine
    s = Engine().new_session()
    _mk_range_table(s, "rdev")
    s.execute("ANALYZE TABLE rdev")
    sql = ("SELECT id, SUM(v) OVER (PARTITION BY p ORDER BY k "
           "RANGE BETWEEN 3 PRECEDING AND 2 FOLLOWING), "
           "COUNT(v) OVER (PARTITION BY p ORDER BY k DESC "
           "RANGE BETWEEN 1 PRECEDING AND CURRENT ROW) FROM rdev")
    want = sorted(map(str, s.query(sql).rows))
    s.vars.update(tidb_tpu_engine="on", tidb_tpu_row_threshold=1,
                  tidb_tpu_strict="on")
    try:
        got = sorted(map(str, s.query(sql).rows))
    finally:
        s.vars.update(tidb_tpu_engine="off", tidb_tpu_strict="off")
    assert got == want


def test_warm_window_launch_count(session):
    """Warm single-fragment window query stays <= slabs + 1 programs:
    the segmented scans ride inside the fused program, not extra
    launches. (A window root is no aggregate: it never becomes a
    statement program, whose ONE launch `tests/test_statement_program.py`
    pins.)"""
    s = session
    sql = DEVICE_WINDOW_QUERIES[0]
    s.vars["tidb_tpu_engine"] = "on"
    s.vars["tidb_tpu_row_threshold"] = 1
    try:
        s.query(sql)               # compile + first touch
        s.query(sql)               # warm
        ph = s.last_guard.phases
        # 800 rows pad into one slab: one fused program (+ finalize)
        assert 1 <= ph.programs_launched <= 2, ph.programs_launched
    finally:
        s.vars["tidb_tpu_engine"] = "off"
