"""FK-aligned join cache (executor/device_cache.AlignedJoin): PK-FK joins
served as pure streams over cached fact-rowspace build columns — the
coprocessor-cache idea (ref: store/copr/coprocessor_cache.go) applied to
join structures. Covers: activation, filter independence, all join kinds,
snowflake chains in both join orders, NULL/missing keys, non-unique
fallback with negative caching, and DML invalidation."""

import numpy as np
import pytest

from tidb_tpu.executor import device_cache
from tidb_tpu.session import Engine


def _on(s):
    s.vars.update(tidb_tpu_engine="on", tidb_tpu_row_threshold=1,
                  tidb_tpu_strict="on")


def _off(s):
    s.vars.update(tidb_tpu_engine="off", tidb_tpu_strict="off")


def _check(s, sql):
    _off(s)
    want = s.query(sql).rows
    _on(s)
    try:
        got = s.query(sql).rows
    finally:
        _off(s)
    assert sorted(map(str, got)) == sorted(map(str, want)), sql
    return want


@pytest.fixture(scope="module")
def s():
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE c (ck BIGINT PRIMARY KEY, seg VARCHAR(8), "
              "nation BIGINT)")
    s.execute("CREATE TABLE o (ok BIGINT PRIMARY KEY, ck BIGINT, d BIGINT, "
              "prio VARCHAR(4))")
    s.execute("CREATE TABLE l (lk BIGINT, price BIGINT, sd BIGINT)")
    rng = np.random.default_rng(11)
    NC, NO, NL = 300, 3000, 60000
    s.execute("INSERT INTO c VALUES " + ",".join(
        f"({i},'s{int(rng.integers(0, 5))}',{int(rng.integers(0, 20))})"
        for i in range(NC)))
    s.execute("INSERT INTO o VALUES " + ",".join(
        f"({i},{int(rng.integers(0, NC))},{int(rng.integers(0, 100))},"
        f"'p{int(rng.integers(0, 4))}')" for i in range(NO)))
    vals = []
    for i in range(NL):
        k = "NULL" if i % 997 == 0 else (
            999999 if i % 499 == 0 else int(rng.integers(0, NO)))
        vals.append(f"({k},{int(rng.integers(0, 1000))},"
                    f"{int(rng.integers(0, 100))})")
    s.execute("INSERT INTO l VALUES " + ",".join(vals))
    for t in ("c", "o", "l"):
        s.execute(f"ANALYZE TABLE {t}")
    return s


def test_aligned_activates_and_matches_cpu(s):
    device_cache.clear()
    _check(s, "SELECT prio, COUNT(*), SUM(price) FROM l JOIN o ON lk = ok "
              "WHERE sd < 50 AND d < 70 GROUP BY prio ORDER BY prio")
    assert any(e.unique for e in device_cache._ALIGNED.values()), \
        "PK-FK join should populate the aligned cache"


def test_aligned_filter_independence(s):
    # one cached structure serves every filter variant (no rebuild)
    _check(s, "SELECT COUNT(*) FROM l JOIN o ON lk = ok WHERE d < 10")
    n = len(device_cache._ALIGNED)
    _check(s, "SELECT COUNT(*) FROM l JOIN o ON lk = ok WHERE d >= 90")
    _check(s, "SELECT prio, SUM(price) FROM l JOIN o ON lk = ok "
              "GROUP BY prio")
    assert len(device_cache._ALIGNED) == n


def test_aligned_join_kinds(s):
    _check(s, "SELECT COUNT(*), SUM(d) FROM l LEFT JOIN o ON lk = ok")
    _check(s, "SELECT COUNT(*) FROM l WHERE lk IN "
              "(SELECT ok FROM o WHERE d < 30)")
    _check(s, "SELECT COUNT(*) FROM l WHERE lk NOT IN (SELECT ok FROM o)")


def test_aligned_snowflake_chain(s):
    # (c ⋈ o) ⋈ l — the dimensions-first order the reorderer prefers:
    # the inner join re-anchors to the fact row space recursively
    device_cache.clear()
    _check(s, "SELECT seg, COUNT(*), SUM(price) FROM l JOIN o ON lk = ok "
              "JOIN c ON o.ck = c.ck WHERE sd < 80 GROUP BY seg "
              "ORDER BY seg")
    kinds = sorted(k[1][0] for k in device_cache._ALIGNED)
    assert kinds == ["al", "col"], kinds   # chained entry + base entry
    # deeper filter on the outermost dimension
    _check(s, "SELECT COUNT(*) FROM l JOIN o ON lk = ok "
              "JOIN c ON o.ck = c.ck WHERE nation < 5 AND d < 50")


def test_aligned_non_unique_falls_back(s):
    s2 = s
    _off(s2)
    s2.execute("CREATE TABLE dup (k BIGINT, v BIGINT)")
    s2.execute("INSERT INTO dup VALUES " + ",".join(
        f"({i % 50},{i})" for i in range(200)))
    s2.execute("ANALYZE TABLE dup")
    _check(s2, "SELECT COUNT(*), SUM(v) FROM l JOIN dup ON lk = k")
    neg = [e for e in device_cache._ALIGNED.values() if not e.unique]
    assert len(neg) == 1, "non-unique build must cache the negative result"


def test_aligned_dml_invalidation(s):
    sql = ("SELECT prio, COUNT(*), SUM(price) FROM l JOIN o ON lk = ok "
           "WHERE d < 70 GROUP BY prio ORDER BY prio")
    _check(s, sql)
    _off(s)
    s.execute("UPDATE o SET d = 0 WHERE ok < 500")
    _check(s, sql)                       # fresh data, fresh structures
    s.execute("DELETE FROM o WHERE ok >= 2900")
    _check(s, sql)                       # FK rows now missing build matches


def test_blocked_expand_beyond_out_cap():
    """A many-to-many join whose fan-out exceeds the device out-cap runs
    as K row-range passes with host-merged agg states — device=True, no
    CPU fallback."""
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE big (k BIGINT, v BIGINT)")
    s.execute("CREATE TABLE m (k BIGINT, w BIGINT)")
    rng = np.random.default_rng(7)
    # 20000 probe rows x avg 8 matches = ~160k output rows; cap at 16384
    # so ~10+ passes are needed, with skew (key 0 is 10x hot)
    keys = np.where(rng.random(20000) < 0.3, 0,
                    rng.integers(0, 200, 20000))
    s.execute("INSERT INTO big VALUES " + ",".join(
        f"({int(k)},{int(rng.integers(0, 50))})" for k in keys))
    s.execute("INSERT INTO m VALUES " + ",".join(
        f"({i % 200},{int(rng.integers(0, 9))})" for i in range(1600)))
    s.execute("ANALYZE TABLE big")
    s.execute("ANALYZE TABLE m")
    sql = ("SELECT w, COUNT(*), SUM(v), MIN(v), AVG(big.k) FROM big "
           "JOIN m ON big.k = m.k GROUP BY w ORDER BY w")
    want = s.query(sql).rows
    s.vars.update(tidb_tpu_engine="on", tidb_tpu_row_threshold=1,
                  tidb_tpu_strict="on", tidb_tpu_join_out_cap=16384)
    try:
        got = s.query(sql).rows
    finally:
        _off(s)
    assert got == want, (got[:3], want[:3])
    # global agg over the same fan-out (no group keys)
    sql2 = "SELECT COUNT(*), SUM(v*w) FROM big JOIN m ON big.k = m.k"
    want2 = s.query(sql2).rows
    s.vars.update(tidb_tpu_engine="on", tidb_tpu_row_threshold=1,
                  tidb_tpu_strict="on", tidb_tpu_join_out_cap=16384)
    try:
        got2 = s.query(sql2).rows
    finally:
        _off(s)
    assert got2 == want2, (got2, want2)


def test_blocked_expand_inside_build_subtree_is_safe():
    """An overflowing join inside an ANCESTOR's build subtree must not
    run blocked (each pass would expose a partial build side to the
    ancestor — double-counted semi matches); results must still match the
    CPU engine via whatever path executes."""
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE lt (lk BIGINT)")
    s.execute("CREATE TABLE big2 (k BIGINT, v BIGINT)")
    s.execute("CREATE TABLE m2 (k BIGINT)")
    rng = np.random.default_rng(9)
    s.execute("INSERT INTO lt VALUES " + ",".join(
        f"({int(rng.integers(0, 300))})" for _ in range(5000)))
    s.execute("INSERT INTO big2 VALUES " + ",".join(
        f"({int(rng.integers(0, 100))},{i})" for i in range(20000)))
    s.execute("INSERT INTO m2 VALUES " + ",".join(
        f"({i % 100})" for i in range(400)))
    for t in ("lt", "big2", "m2"):
        s.execute(f"ANALYZE TABLE {t}")
    sql = ("SELECT COUNT(*) FROM lt WHERE lk IN "
           "(SELECT big2.v FROM big2 JOIN m2 ON big2.k = m2.k)")
    want = s.query(sql).rows
    # strict OFF: the correct behavior here is CPU fallback, not blocked
    s.vars.update(tidb_tpu_engine="on", tidb_tpu_row_threshold=1,
                  tidb_tpu_join_out_cap=8192)
    try:
        got = s.query(sql).rows
    finally:
        _off(s)
    assert got == want, (got, want)


# ---- a structure follows both tables' generations, or is rebuilt: never
# ---- stale positions, and every rebuild is counted -----------------------

JQ = ("SELECT prio, COUNT(*), SUM(price) FROM l JOIN o ON lk = ok "
      "WHERE d < 70 GROUP BY prio ORDER BY prio")


def _pair():
    eng = Engine()
    eng.global_vars["tidb_enable_auto_analyze"] = False
    s = eng.new_session()
    s.execute("CREATE TABLE o (ok BIGINT PRIMARY KEY, d BIGINT, "
              "prio VARCHAR(4))")
    s.execute("CREATE TABLE l (lk BIGINT, price BIGINT)")
    rng = np.random.default_rng(1)
    s.execute("INSERT INTO o VALUES " + ",".join(
        f"({i},{i % 100},'p{i % 4}')" for i in range(3000)))
    s.execute("INSERT INTO l VALUES " + ",".join(
        f"({int(rng.integers(0, 3000))},{i % 1000})" for i in range(40000)))
    for t in "ol":
        s.execute(f"ANALYZE TABLE {t}")
    s.vars["tidb_tpu_compaction"] = "off"
    return s


def _aligned_declines():
    from tidb_tpu.util.observability import REGISTRY
    return {dict(labels)["gate"]: v
            for (name, labels), v in REGISTRY.counters.items()
            if name == "tidb_tpu_delta_declines_total"
            and dict(labels)["gate"].startswith("aligned-")}


def test_an_updated_build_row_keeps_its_fact_rows():
    """UPDATE of a build row is its key dying and arriving in one step:
    the fact rows that carried it dangle, live, while it arrives — the
    structure is rebuilt (counted), not advanced past them."""
    s = _pair()
    _check(s, JQ)
    s.execute("INSERT INTO l VALUES (5, 7)")
    _check(s, JQ)
    before = _aligned_declines().get("aligned-dangling", 0)
    s.execute("UPDATE o SET d = 0 WHERE ok = 5")
    _check(s, JQ)
    assert _aligned_declines().get("aligned-dangling", 0) == before + 1
    s.execute("UPDATE o SET d = 99 WHERE ok < 500")
    _check(s, JQ)


def test_scattered_dead_build_keys_advance_without_a_rebuild():
    """Dead keys in more runs than `ALIGNED_MAX_RANGES` unmatch their fact
    rows by asking the lookup table (no dependence on contiguous keys);
    a purge by key range compares ranges. Neither rebuilds."""
    s = _pair()
    _check(s, JQ)
    before = dict(_aligned_declines())
    s.execute("DELETE FROM o WHERE ok >= 2900")          # one range
    _check(s, JQ)
    s.execute("DELETE FROM o WHERE ok % 7 = 3")          # ~400 runs
    _check(s, JQ)
    assert _aligned_declines() == before
    # (live fact rows now match no build row: a build row that arrives may
    # be theirs, so its arrival rebuilds — counted)
    s.execute("INSERT INTO o VALUES (2950, 1, 'p1')")
    _check(s, JQ)
    assert _aligned_declines().get("aligned-dangling", 0) == \
        before.get("aligned-dangling", 0) + 1


def test_a_compaction_moves_rows_under_an_unchanged_table():
    """A compaction that keeps the table's data, capacity and slab count
    still moves rows: the structure's positions are of another base build
    (`AlignedJoin.space`), and it is rebuilt."""
    from tidb_tpu.executor import delta
    delta.run_pending_compactions()              # (earlier tests' jobs)
    s = _pair()
    _check(s, JQ)
    s.execute("DELETE FROM l WHERE lk < 400")    # ≥ 1/8 dead, cap unchanged
    _check(s, JQ)
    device_cache._READERS.clear()                # (no warm-up: a stale swap)
    assert delta.pending_compactions() == 1
    assert delta.run_pending_compactions() == 1
    _check(s, JQ)
    s.execute("DELETE FROM l WHERE lk < 800")
    _check(s, JQ)
    # and with the warm-up: the structure over the new positions is built
    # before the swap and installed with it
    before = dict(_aligned_declines())
    assert delta.run_pending_compactions() == 1
    _check(s, JQ)
    assert _aligned_declines() == before


# ---- a structure's arrays in the fact's row space are ONE array each once a
# ---- statement program reads them (PR 46) ----------------------------------

def _stacks() -> float:
    from tidb_tpu.util.observability import REGISTRY
    return sum(v for (n, _ls), v in list(REGISTRY.counters.items())
               if n == "tidb_tpu_slab_stacks_total")


def _slices() -> float:
    from tidb_tpu.util.observability import REGISTRY
    return sum(v for (n, _ls), v in list(REGISTRY.counters.items())
               if n == "tidb_tpu_slab_slices_total")


def _structures(s) -> list:
    """This session's engine's (unique) aligned structures."""
    return [a for k, a in list(device_cache._ALIGNED.items())
            if k[0] == id(s.engine.store) and a.unique]


def test_generations_of_a_structure_share_what_no_commit_rewrites():
    """Five fact slabs under a statement program: the match mask and the
    gathered build columns are stacked once (`tidb_tpu_slab_stacks_total`),
    with the fact's columns. The FIRST commit that kills build rows
    unmatches the fact rows that carried their keys — ONE program over the
    stacks, a new stacked mask of the new generation's own — and stacks
    what it alone reads, the matched build rows, that once; it shares
    every other stack with the structure kept behind it by identity. No
    later commit, no warm statement and no read of the kept generation
    stacks anything again, and `hbm_bytes()` / the kept bytes count a
    stacked array once."""
    from tidb_tpu.executor import agg_slabs
    from tidb_tpu.util.observability import REGISTRY
    s = _pair()
    s.vars["tidb_tpu_max_slab_rows"] = 8192        # 40000 rows: 5 slabs
    agg_slabs._SPEC_CACHE.clear()
    for _ in range(3):
        _check(s, JQ)                              # cold, whole, warm
    (old,) = _structures(s)
    assert old.matched.is_stacked and old.matched.n_base == 5
    assert all(c.is_stacked for c in old.cols.values())
    # (no statement program reads the matched rows: the first unmatch
    # of dead build keys does, beside the match mask, and stacks them)
    assert not old.midx.is_stacked
    s.execute("INSERT INTO l VALUES (5, 7)")       # the first generation:
    s.execute("DELETE FROM l WHERE price = 999")   # delta slab and masks
    for _ in range(3):
        _check(s, JQ)
    (mid,) = _structures(s)
    assert mid is not old and len(mid.matched) == 6
    stacks = _stacks()
    declines = dict(_aligned_declines())
    s.execute("DELETE FROM o WHERE ok >= 2900")    # build rows die
    _check(s, JQ)
    _check(s, JQ)
    (new,) = _structures(s)
    assert new is not mid and new.kept == (mid,)
    assert _aligned_declines() == declines
    # the match masks: rewritten whole, on the device, in one array
    assert new.matched.is_stacked
    assert new.matched.stack_leaf() is not mid.matched.stack_leaf()
    assert new.matched.stack_leaf().shape == mid.matched.stack_leaf().shape
    # everything else of the base: the same arrays
    for c in new.cols:
        assert [id(a) for s_, a in new.cols[c].arrays() if s_ < 5] == \
            [id(a) for s_, a in mid.cols[c].arrays() if s_ < 5]
    assert new.midx.base is mid.midx.base and new.midx.is_stacked
    assert _stacks() == stacks + 1, "the matched build rows, once"
    stacks += 1
    slices = _slices()
    s.execute("DELETE FROM o WHERE ok >= 2800 AND ok < 2900")
    _check(s, JQ)
    _check(s, JQ)
    assert _stacks() == stacks, "a commit or a warm statement stacked"
    assert _slices() == slices, "… or sliced a slab out of a stack"
    (newest,) = _structures(s)
    owned = list(newest._owned())
    assert len({id(a) for a in owned}) == len(owned)
    assert newest.hbm_bytes() == sum(a.nbytes for a in owned) \
        + newest.kept_bytes
    held = {id(a) for a in owned}
    (kept,) = newest.kept
    assert newest.kept_bytes == sum(
        a.nbytes for a in {id(a): a for a in kept._owned()
                           if id(a) not in held}.values())
    assert REGISTRY.counters[("tidb_tpu_delta_generations_kept_bytes", ())] \
        >= newest.kept_bytes


def test_the_first_unmatch_stacks_what_no_statement_program_did():
    """Build rows die under a five-slab fact BEFORE any statement program
    was built over it (one cold execution: per-slab programs, lists). The
    unmatch of their keys has ONE form over the base slabs — the program
    whose loop indexes the stacks — so it stacks what it reads then (the
    fact's key column, the match mask, the matched build rows; the fact
    has no masks yet: made from the live prefixes, born stacked), once:
    the next dead keys find the stacks and move no counter. The answers
    stay the reference's and the structure is advanced, not rebuilt."""
    from tidb_tpu.executor import agg_slabs
    s = _pair()
    s.vars["tidb_tpu_max_slab_rows"] = 8192        # 40000 rows: 5 slabs
    agg_slabs._SPEC_CACHE.clear()
    _check(s, JQ)                                  # cold: lists
    (old,) = _structures(s)
    assert not old.matched.is_stacked and old.matched.n_base == 5
    stacks, declines = _stacks(), dict(_aligned_declines())
    s.execute("DELETE FROM o WHERE ok >= 2900")
    _check(s, JQ)
    (new,) = _structures(s)
    assert new is not old and _aligned_declines() == declines
    assert new.matched.is_stacked and new.midx.is_stacked
    assert new.midx.base is old.midx.base          # (stacked where it lay)
    assert _stacks() > stacks
    slices = _slices()
    s.execute("DELETE FROM o WHERE ok >= 2800 AND ok < 2900")
    _check(s, JQ)
    _check(s, JQ)
    # (the second execution's statement program stacks the columns IT
    # reads and no unmatch did; the match structures' stay as they are)
    (newest,) = _structures(s)
    assert newest.matched.stack_leaf() is not new.matched.stack_leaf()
    assert newest.midx.base is new.midx.base
    assert _aligned_declines() == declines
    assert _slices() == slices, "an unmatch sliced a slab out of a stack"
