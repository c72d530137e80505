"""One launch a warm aggregate statement (`agg_slabs._StatementProgram`).

Once an earlier execution of a statement's digest has settled its
capacities and every slab is resident, `_run_agg_slabs` issues the slab
bodies and the merge or fused finalize as ONE traced program under ONE hold
of the batch slot (`launch_plan=whole` on the `device.fragment` span,
`tidb_tpu_statement_programs_total{plan=whole}`); the loop over slabs stays
the cold and the escalating path (`launch_plan=slabs:<why>`). These tests
pin: the answers of both plans are the host's, over chains and join trees,
under zone-map pruning, over a delta generation (delta slab, liveness
masks) and over one slab; the second execution of a digest is whole, the
third launches exactly one program and traces nothing; an overflow read
back from a statement program is answered by the per-slab driver; sorted
runs, DISTINCT pair sets and a cold stream never leave it; and a cached
statement program holds nothing of the statement that built it; and a
DOUBLE sum is the per-slab driver's to the bit.

How a statement program reads its slabs (PR 46): the device cache holds a
column's base slabs as ONE array a leaf (`device_cache.SlabColumn`, stacked
when the first statement program over the table is built) and the loop
indexes it — over six slabs, with the first, a middle or the last slab
pruned, over a delta generation with one generation kept behind it, over a
join tree's FK-aligned columns, over one slab; no `conditional` in the
lowered program, and `tidb_tpu_slab_stacks_total` moves once a column.
"""

import functools

import pytest

from tidb_tpu.executor import device_cache as dc
from tidb_tpu.executor import agg_slabs, compile_cache, tree_fragment
from tidb_tpu.executor.tree_fragment import TreeProgram
from tidb_tpu.session import Engine
from tidb_tpu.util import failpoint, timeline
from tidb_tpu.util.observability import REGISTRY

ROWS, SLAB = 3000, 1024             # three slabs


@pytest.fixture
def db():
    eng = Engine()
    eng.global_vars["tidb_enable_auto_analyze"] = False
    s = eng.new_session()
    s.execute("CREATE TABLE d (id INT PRIMARY KEY, name VARCHAR(8))")
    s.execute("INSERT INTO d VALUES " + ",".join(
        f"({i}, 'n{i % 5}')" for i in range(8)))
    # `a` ascends, so zone maps can skip slabs; `k` has 300 values
    s.execute("CREATE TABLE f (a BIGINT, k BIGINT, b INT, v BIGINT, "
              "c VARCHAR(8))")
    s.execute("INSERT INTO f VALUES " + ",".join(
        f"({i}, {i % 300}, {i % 8}, {(i * 37) % 211 - 100}, 'c{i % 3}')"
        for i in range(ROWS)))
    s.execute("ANALYZE TABLE d")
    s.execute("ANALYZE TABLE f")
    s.vars.update({"tidb_tpu_engine": "on", "tidb_tpu_row_threshold": 1,
                   "tidb_tpu_max_slab_rows": SLAB,
                   "tidb_tpu_compaction": "off"})
    agg_slabs._SPEC_CACHE.clear()
    yield eng, s
    eng.close()
    dc.clear()


def oracle(s, sql):
    s.vars["tidb_tpu_engine"] = "off"
    try:
        return s.query(sql).rows
    finally:
        s.vars["tidb_tpu_engine"] = "on"


def run(s, sql):
    """One execution → (rows, launch plan, launched program names, traces)."""
    traces = compile_cache.PROGRAM_TRACES
    with timeline.capture() as cap:
        rows = s.query(sql).rows
    assert s.last_engine == "tpu", sql
    evs = [e for e in cap.events if e["ph"] == "X"]
    # (an aggregate's fragment says its plan; a row root has none)
    (plan,) = [e["args"]["launch_plan"] for e in evs
               if e["name"] == "device.fragment"
               and "launch_plan" in e["args"]] or [None]
    return (rows, plan,
            [e["name"] for e in sorted(evs, key=lambda e: e["ts"])
             if e["cat"] == "launch"],
            compile_cache.PROGRAM_TRACES - traces)


def same(rows, want, sql):
    if "ORDER BY" not in sql:
        rows, want = sorted(map(str, rows)), sorted(map(str, want))
    assert rows == want, sql


def thrice(s, sql, first="slabs:", slabs=3):
    """A digest's first three executions: a launch a slab (+ the tail),
    then the statement program (traced there, unless an earlier test left
    it in the process's compile cache), then exactly ONE launch and no
    trace; every answer the host's. → the statement program's name."""
    want = oracle(s, sql)
    rows, plan, launched, _t = run(s, sql)
    same(rows, want, sql)
    assert plan.startswith(first), plan
    assert slabs <= len(launched) <= slabs + 1, launched
    rows, plan, launched, _t = run(s, sql)
    same(rows, want, sql)
    assert plan == "whole" and len(launched) == 1
    rows, plan, again, traced = run(s, sql)
    same(rows, want, sql)
    assert (plan, again, traced) == ("whole", launched, 0)
    assert s.last_guard.phases.programs_launched == 1
    return launched[0]


_JOIN = "FROM f JOIN d ON f.b = d.id "
STATEMENTS = {
    "chain-bounds": "SELECT b, COUNT(*), SUM(v) FROM f GROUP BY b",
    "chain-global": "SELECT COUNT(*), SUM(v), MIN(a), AVG(k) FROM f "
                    "WHERE v > -50",
    "chain-factorize": "SELECT v % 7, COUNT(*), MAX(a) FROM f "
                       "GROUP BY v % 7",
    "chain-string-key": "SELECT c, COUNT(*), SUM(v) FROM f GROUP BY c "
                        "ORDER BY c",
    "chain-topn": "SELECT b, COUNT(*), SUM(v) FROM f GROUP BY b "
                  "ORDER BY SUM(v) DESC, b LIMIT 3",
    "chain-rollup": "SELECT b, c, COUNT(*) FROM f GROUP BY b, c "
                    "WITH ROLLUP",
    "tree-bounds": "SELECT d.name, COUNT(*), SUM(f.v) " + _JOIN +
                   "GROUP BY d.name",
    "tree-sort": "SELECT d.name, COUNT(*), SUM(f.v) " + _JOIN +
                 "GROUP BY d.name ORDER BY d.name",
    "tree-global": "SELECT COUNT(*), SUM(f.v) " + _JOIN +
                   "WHERE d.name <> 'n1'",
    "tree-factorize": "SELECT f.v % 5, MAX(d.id), COUNT(*) " + _JOIN +
                      "GROUP BY f.v % 5",
}


@pytest.mark.parametrize("case", sorted(STATEMENTS))
def test_whole_statement_answers_equal_the_per_slab_drivers(db, case):
    _, s = db
    name = thrice(s, STATEMENTS[case])
    kind = "stmt_fused" if case.startswith("tree") else "stmt_chain"
    assert name.startswith(kind + "_"), name


def test_the_plan_is_tagged_and_counted(db):
    """`launch_plan` on the `device.fragment` span and
    `tidb_tpu_statement_programs_total{plan=}`: a statement counts once,
    under the plan that answered it."""
    _, s = db

    def counted():
        return {p: REGISTRY.counters.get(
            ("tidb_tpu_statement_programs_total", (("plan", p),)), 0)
            for p in ("whole", "slabs")}

    sql = STATEMENTS["chain-bounds"]
    before = counted()
    plans = [run(s, sql)[1] for _ in range(3)]
    assert plans == ["slabs:cold", "whole", "whole"]
    after = counted()
    assert after["slabs"] - before["slabs"] == 1
    assert after["whole"] - before["whole"] == 2
    # a second statement over the now resident table misses only the
    # specialization
    assert run(s, STATEMENTS["chain-topn"])[1] == "slabs:spec-miss"


DOUBLES = {
    "sum-avg": "SELECT c, COUNT(*), SUM(x), AVG(x) FROM g GROUP BY c",
    "global": "SELECT SUM(x), AVG(x), COUNT(*) FROM g WHERE a % 3 <> 1",
    "topn": "SELECT b, SUM(x) FROM g GROUP BY b ORDER BY SUM(x) DESC, b "
            "LIMIT 4",
    "variance": "SELECT c, VAR_POP(x), STDDEV_SAMP(x) FROM g GROUP BY c",
    "tree": "SELECT d.name, SUM(g.x), AVG(g.x) FROM g JOIN d ON g.b = d.id "
            "GROUP BY d.name",
}


@pytest.mark.parametrize("seed", [3, 11])
@pytest.mark.parametrize("case", sorted(DOUBLES))
def test_a_double_sum_is_the_per_slab_drivers_to_the_bit(db, case, seed):
    """A floating-point sum depends on the order of its additions. The
    slab bodies are the same traced functions in both plans, and the merge
    folds a float sum partial by partial in slab order
    (`device_emit.emit_merge`, `AggFunc.float_sums`), so a statement
    program answers what the per-slab driver answered, to the last bit:
    over the base slabs, and over a delta generation, where the loop over
    the base slabs is followed by the delta slab's body."""
    import numpy as np
    _, s = db
    rng = np.random.default_rng(seed)
    s.execute("CREATE TABLE g (a BIGINT, b INT, x DOUBLE, c VARCHAR(8))")
    xs = rng.normal(size=ROWS) * 10.0 ** rng.integers(-3, 4, size=ROWS)
    cs = rng.integers(0, 4, size=ROWS)
    s.execute("INSERT INTO g VALUES " + ",".join(
        f"({i}, {i % 8}, {xs[i]:.6f}, 'c{cs[i]}')" for i in range(ROWS)))
    sql = DOUBLES[case]

    def both():
        per_slab, plan, _l, _t = run(s, sql)
        assert plan.startswith("slabs:")
        for _ in range(2):
            whole, plan, launched, _t = run(s, sql)
            assert plan == "whole" and len(launched) == 1
            same(whole, per_slab, sql)      # text-equal: every bit
        return per_slab

    first = both()
    s.execute("INSERT INTO g VALUES " + ",".join(
        f"({ROWS + i}, {i % 8}, {float(rng.normal()):.6f}, 'c{i % 4}')"
        for i in range(5)))
    s.execute("DELETE FROM g WHERE a % 101 = 7")
    assert both() != first


def test_a_statement_program_compiles_where_it_is_built_not_in_the_slot(db):
    """A statement-sized compile is seconds on the chip's host. It runs
    when the program is built — under the signature's build lock, by one
    statement — and the launch that follows, inside the batch slot, finds
    the executable: no compile overlaps a hold of the slot."""
    _, s = db
    sql = "SELECT b, MIN(v), MAX(k), COUNT(*) FROM f WHERE v <> 13 GROUP BY b"
    run(s, sql)
    with timeline.capture() as cap:
        s.query(sql)
    evs = [e for e in cap.events if e["ph"] == "X"]
    holds = [(e["ts"], e["ts"] + e["dur"]) for e in evs
             if e["name"].startswith("sched-slot")]
    compiles = [(e["ts"], e["ts"] + e["dur"]) for e in evs
                if e["name"] == "jax.backend_compile"]
    (launch,) = [e for e in evs if e["cat"] == "launch"]
    assert launch["name"].startswith("stmt_chain_") and holds and compiles
    assert not [(c, h) for c in compiles for h in holds
                if c[0] < h[1] and h[0] < c[1]]
    assert max(c[1] for c in compiles) <= launch["ts"]


@pytest.fixture
def built(monkeypatch):
    """The statement programs asked for: (signature, slabs)."""
    asked = []
    real = agg_slabs.get_statement_program

    def recording(src, prog, n_run, *tail):
        sprog = real(src, prog, n_run, *tail)
        asked.append((sprog.sig, n_run))
        return sprog

    monkeypatch.setattr(agg_slabs, "get_statement_program", recording)
    return asked


@pytest.mark.parametrize("lo,survive", [(0, 3), (1024, 2), (2048, 1)])
def test_zone_map_pruning_changes_the_program_not_the_answer(
        db, built, lo, survive):
    """How MANY slabs survive joins the statement program's signature
    (`slabs=<n>`: the loop's trip count); the answers stay the host's."""
    _, s = db
    for sql in (f"SELECT COUNT(*), SUM(v) FROM f WHERE a >= {lo}",
                f"SELECT d.name, COUNT(*) {_JOIN}WHERE f.a >= {lo} "
                "GROUP BY d.name ORDER BY d.name"):
        del built[:]
        thrice(s, sql, slabs=survive)
        assert built and {b for _sig, b in built} == {survive}, built
        assert all(f"|slabs={survive}|" in sig for sig, _b in built)
        assert s.last_guard.phases.slabs_skipped == 3 - survive


def test_one_slab_folds_the_finalize_into_the_launch(db, built):
    """A one-slab table takes the same path: partial + finalize in one
    program, and without an ORDER BY the one partial is the answer."""
    _, s = db
    s.vars["tidb_tpu_max_slab_rows"] = 4096
    s.execute("CREATE TABLE one (b INT, v BIGINT)")
    s.execute("INSERT INTO one VALUES " + ",".join(
        f"({i % 6}, {i})" for i in range(500)))
    thrice(s, "SELECT b, SUM(v) FROM one GROUP BY b ORDER BY b", slabs=1)
    thrice(s, "SELECT b, SUM(v) FROM one GROUP BY b", slabs=1)
    assert [b for _sig, b in built] == [1] * 4


@pytest.mark.parametrize("case", ["chain-bounds", "chain-topn",
                                  "chain-global", "tree-sort",
                                  "tree-bounds"])
def test_a_delta_generation_runs_whole_with_its_delta_slab_and_masks(
        db, built, case):
    """`tests/test_delta_slabs.py`'s shapes: appended rows in the raw
    delta slab (a body of its own shape, after the loop over the base
    slabs), deleted rows as cleared bits of the liveness masks (the
    body's masked variant). The first
    write changes the table's shapes, so its first read specializes
    again; a later write costs the next read neither a specialization nor
    a trace: ONE launch."""
    eng, s = db
    sql = STATEMENTS[case]
    thrice(s, sql)
    assert all("|delta=-|" in sig for sig, _b in built)
    s.execute("INSERT INTO f VALUES (5000, 7, 3, 41, 'c1'), "
              "(5001, 8, 4, -17, 'c2')")
    s.execute("DELETE FROM f WHERE a % 97 = 5")
    del built[:]
    thrice(s, sql, first="slabs:spec-miss", slabs=4)
    assert built and all(b == 4 and "|delta=-|" not in sig
                         for sig, b in built), built
    # a tombstone in the delta slab itself, another append, another base
    # tombstone: same shapes, so the digest stays specialized
    s.execute("DELETE FROM f WHERE a = 5001")
    s.execute("INSERT INTO f VALUES (5002, 9, 5, 12, 'c0')")
    s.execute("DELETE FROM f WHERE a = 77")
    rows, plan, launched, traced = run(s, sql)
    same(rows, oracle(s, sql), sql)
    assert (plan, len(launched), traced) == ("whole", 1, 0)


def test_a_forced_overflow_answers_through_the_per_slab_driver(db):
    """The failpoint's VALUE at the capacity boundary reads as an overflow
    of a statement program: the per-slab driver answers at the same
    capacities (`launch_plan=slabs:overflow`), and the counter says so."""
    _, s = db
    for sql, n in ((STATEMENTS["chain-topn"], 3),
                   (STATEMENTS["tree-sort"], 3)):
        thrice(s, sql)
        whole = ("tidb_tpu_statement_programs_total", (("plan", "whole"),))
        slabs = ("tidb_tpu_statement_programs_total", (("plan", "slabs"),))
        before = (REGISTRY.counters.get(whole, 0),
                  REGISTRY.counters.get(slabs, 0))
        with failpoint.enabled("fused-pipeline-overflow", value=True,
                               times=1):
            rows, plan, launched, _t = run(s, sql)
        same(rows, oracle(s, sql), sql)
        assert plan == "slabs:overflow"
        # the statement program, then a program a slab and the finalize
        assert len(launched) == 1 + n + 1 and \
            launched[0].startswith("stmt_"), launched
        assert (REGISTRY.counters.get(whole, 0),
                REGISTRY.counters.get(slabs, 0)) == (before[0],
                                                     before[1] + 1)
        assert run(s, sql)[1] == "whole"      # and the next is whole again


def test_a_real_group_overflow_escalates_through_the_per_slab_driver(db):
    """A capacity the digest settled on stops holding: groups arrive that
    the specialized cap cannot hold. The statement program's control
    fetch shows it, the per-slab driver re-runs and the ladder resizes;
    the next execution is whole at the new cap."""
    _, s = db
    s.vars["tidb_tpu_group_cap"] = 16
    sql = "SELECT v % 1000, COUNT(*) FROM f GROUP BY v % 1000"
    s.query(sql)                                    # resident, then the
    s.execute("INSERT INTO f VALUES (4000, 1, 1, 1, 'c0')")   # delta shapes
    want = oracle(s, sql)
    plans = []
    for _ in range(3):          # the first climbs the ladder from 16 slots
        rows, plan, _launched, _t = run(s, sql)
        same(rows, want, sql)
        plans.append(plan)
    assert plans == ["slabs:spec-miss", "whole", "whole"]
    # hundreds of new groups in the delta slab, same shapes: a spec HIT
    s.execute("INSERT INTO f VALUES " + ",".join(
        f"({4100 + i}, 1, 1, {1000 + i}, 'c0')" for i in range(400)))
    rows, plan, launched, _t = run(s, sql)
    same(rows, oracle(s, sql), sql)
    assert plan == "slabs:overflow", plan
    assert launched[0].startswith("stmt_chain_") and len(launched) > 5
    rows, plan, launched, _t = run(s, sql)      # traces at the new cap
    same(rows, oracle(s, sql), sql)
    assert plan == "whole" and len(launched) == 1
    assert run(s, sql)[1:] == ("whole", launched, 0)


def test_distinct_pairs_and_a_cold_stream_stay_per_slab(db):
    _, s = db
    sql = "SELECT b, COUNT(DISTINCT v) FROM f GROUP BY b ORDER BY b"
    want = oracle(s, sql)
    for _ in range(3):
        rows, plan, launched, _t = run(s, sql)
        assert rows == want and plan == "slabs:pairs"
        assert len(launched) == 4
    # a statement whose column is not resident streams its first touch
    assert run(s, "SELECT c, MAX(a) FROM f GROUP BY c")[1] == "slabs:cold"


def test_sorted_runs_stay_on_their_own_driver(db, monkeypatch):
    _, s = db
    monkeypatch.setattr(tree_fragment, "SLOT_ADDRESS_CAP", 64)
    sql = ("SELECT k, COUNT(*), SUM(v) FROM f GROUP BY k "
           "ORDER BY SUM(v) DESC, k LIMIT 5")
    want = oracle(s, sql)
    for _ in range(3):
        rows, plan, launched, _t = run(s, sql)
        assert rows == want and plan == "slabs:runs"
        assert [n.rpartition("_")[0] for n in launched] == \
            ["partial_chain"] * 3 + ["sort_rows", "finalize"]


def test_live_rows_are_device_values_of_the_entry(db):
    """The slabs' live-row counts are uploaded once a table version and
    handed to every launch as they are (no `jnp.int32(n)`, no
    `np.array([rows])` inside a `launch` span); a generation with masks
    hands the masks."""
    eng, s = db
    s.query(STATEMENTS["chain-bounds"])
    tid = eng.catalog.info_schema.table("f").id
    (ent,) = [e for (_d, sid, t, _p), e in dc.CACHE.items()
              if sid == id(eng.store) and t == tid]
    lives = [ent.live_arg(i) for i in range(ent.n_slabs)]
    assert [int(n) for n in lives] == [1024, 1024, ROWS - 2048]
    assert all(a is b for a, b in zip(
        lives, (ent.live_arg(i) for i in range(ent.n_slabs))))
    assert ent.live_counts() is ent.live_counts()
    assert ent.live_counts(frozenset({1})).tolist() == [1024, 0, 952]
    s.execute("DELETE FROM f WHERE a = 3")
    s.query(STATEMENTS["chain-bounds"])
    (new,) = [e for (_d, sid, t, _p), e in dc.CACHE.items()
              if sid == id(eng.store) and t == tid]
    # (three slabs on one device: the masks are ONE array from their
    # birth, and a launch takes the array itself and the slab's row)
    assert new is not ent and new.alive.is_stacked
    assert new.live_arg(0).a is new.alive.stack_leaf()


def test_a_cached_statement_program_holds_nothing_of_a_statement(db):
    """The compile cache outlives statements and tables: a statement
    program keeps the slab programs and static functions, never a source,
    an entry or a device array (a superseded generation must free its
    arrays by reference count)."""
    _, s = db
    for case in ("chain-topn", "tree-sort"):
        thrice(s, STATEMENTS[case])
    progs = [p for p in compile_cache._COMPILE_CACHE.values()
             if isinstance(p, agg_slabs._StatementProgram)]
    assert progs
    for p in progs:
        assert p.control in (agg_slabs.ChainSlabs.control,
                             agg_slabs.TreeSlabs.control)
        for body in filter(None, (p.body, p.dbody)):
            assert isinstance(body, functools.partial)
            assert body.func in (agg_slabs.ChainSlabs._slab_body,
                                 agg_slabs.TreeSlabs._slab_body)
            assert all(isinstance(a, (agg_slabs._FragmentProgram,
                                      TreeProgram, int))
                       for a in body.args), body.args


def test_the_control_fetch_packs_into_a_vector_a_kind_and_back():
    """What a statement program hands the host is ONE vector for every
    integer and boolean leaf and one a float dtype; the host cuts them
    back into the tree by the shapes and dtypes kept at trace time."""
    import numpy as np
    from tidb_tpu.ops.jax_env import jax, jnp
    tree = {"ngs": jnp.array([3, 5], dtype=jnp.int32),
            "ng": jnp.int32(7),
            "keys": [(jnp.arange(4, dtype=jnp.int64) - 2 ** 40,
                      jnp.array([True, False, True, True]))],
            "states": [(jnp.array([[1, 2], [3, 4]], dtype=jnp.uint32),
                        jnp.array([0.5, -1.25], dtype=jnp.float32)),
                       (jnp.zeros((0,), dtype=jnp.int64),)]}
    packed = jax.device_get(jax.jit(agg_slabs._pack)(tree))
    assert sorted(packed) == ["float32", "int64"]
    like = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        tree)
    back = agg_slabs._unpack(packed, like)
    for got, want in zip(jax.tree.leaves(back), jax.tree.leaves(tree)):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert np.array_equal(got, np.asarray(want))


# ---------------------------------------------------------------------------
# the loop indexes ONE array a column (PR 46)
# ---------------------------------------------------------------------------

SIX = 6 * SLAB - 100        # six slabs, the last one short


@pytest.fixture
def six(db):
    """`g`: six slabs. `a` ascends (zone maps skip a prefix or a suffix);
    `z` is 0 in slab 2 alone (`z > 0` skips a MIDDLE slab)."""
    eng, s = db
    s.execute("CREATE TABLE g (a BIGINT, z INT, b INT, v BIGINT, "
              "c VARCHAR(8))")
    s.execute("INSERT INTO g VALUES " + ",".join(
        f"({i}, {0 if 2 * SLAB <= i < 3 * SLAB else 1 + i % 9}, {i % 8}, "
        f"{(i * 37) % 211 - 100}, 'c{i % 3}')" for i in range(SIX)))
    s.execute("ALTER TABLE d ADD COLUMN w BIGINT")
    s.execute("UPDATE d SET w = id * 11 + 3")
    s.execute("ANALYZE TABLE g")
    s.execute("ANALYZE TABLE d")
    return eng, s


def _stacks(table=None) -> float:
    return sum(v for (n, ls), v in list(REGISTRY.counters.items())
               if n == "tidb_tpu_slab_stacks_total"
               and (table is None or ("table", str(table)) in ls))


def _slices() -> float:
    return sum(v for (n, _ls), v in list(REGISTRY.counters.items())
               if n == "tidb_tpu_slab_slices_total")


def _cached(eng, name):
    tid = eng.catalog.info_schema.table(name).id
    (ent,) = [e for (_d, sid, t, p), e in dc.CACHE.items()
              if sid == id(eng.store) and t == tid and p is None]
    return tid, ent


_GJOIN = "FROM g JOIN d ON g.b = d.id "
SIX_SLABS = {
    # case: (statement, surviving slabs)
    "six": ("SELECT b, COUNT(*), SUM(v), MIN(a) FROM g GROUP BY b", 6),
    "first-missing": ("SELECT c, COUNT(*), SUM(v) FROM g "
                      f"WHERE a >= {SLAB} GROUP BY c", 5),
    "middle-missing": ("SELECT b, COUNT(*), SUM(v) FROM g WHERE z > 0 "
                       "GROUP BY b ORDER BY b", 5),
    "last-missing": (f"SELECT COUNT(*), SUM(v), MAX(a) FROM g "
                     f"WHERE a < {5 * SLAB}", 5),
    "first-and-last-missing": (
        "SELECT b, COUNT(*) FROM g "
        f"WHERE a >= {SLAB} AND a < {5 * SLAB} GROUP BY b", 4),
    "tree-aligned": ("SELECT d.name, COUNT(*), SUM(g.v), SUM(d.w) " + _GJOIN
                     + "GROUP BY d.name ORDER BY d.name", 6),
    "tree-aligned-pruned": (
        "SELECT d.name, SUM(d.w), MAX(g.a) " + _GJOIN
        + f"WHERE g.a >= {2 * SLAB} GROUP BY d.name ORDER BY d.name", 4),
}


@pytest.mark.parametrize("case", sorted(SIX_SLABS))
def test_the_loop_indexes_one_array_a_column(six, built, monkeypatch, case):
    """The looped statement program's answer is the per-slab driver's (the
    digest's first execution) and the host's, over six base slabs and with
    the first, a middle, the last slab pruned — which rows of the stacks
    the turns read is an ARGUMENT (`picks`), the program names only how
    many — and over a join tree whose FK-aligned match mask and gathered
    build columns are indexed the same way. Its lowered HLO holds no
    `conditional`; every column it reads is stacked once."""
    eng, s = six
    sql, survive = SIX_SLABS[case]
    launched = []
    real = agg_slabs._StatementProgram.__init__

    def init(self, *a):
        real(self, *a)
        launched.append((self, a[-1]))
    monkeypatch.setattr(agg_slabs._StatementProgram, "__init__", init)
    tid = eng.catalog.info_schema.table("g").id
    stacks0, of_g0 = _stacks(), _stacks(tid)
    name = thrice(s, sql, slabs=survive)
    assert name.startswith("stmt_fused_" if "tree" in case
                           else "stmt_chain_")
    assert {b for _sig, b in built} == {survive}
    assert s.last_guard.phases.slabs_skipped == 6 - survive
    tid, ent = _cached(eng, "g")
    used = [c for c in ent.dev.values() if c.is_stacked]
    assert used and all(len(c) == 6 and c.n_base == 6 for c in used)
    # (a column, and in the tree the match mask, the matched build rows'
    # gathered columns: each once)
    assert _stacks() - stacks0 == _stacks(tid) - of_g0 >= len(used)
    for sprog, args in launched:    # (none, if an earlier test built it)
        hlo = sprog.run.lower(*args).as_text(dialect="hlo")
        assert "conditional" not in hlo and "dynamic-slice" in hlo
        _shared, base, _delta, picks = args
        # (one vector of stack rows; a second where this statement's own
        # cold first touch left the columns holes: they stack their
        # resident slabs alone, the live-row counts every slab's)
        assert 1 <= len(picks) <= 2
        assert {int(v.shape[0]) for v in picks} == {survive}
        assert sprog.said is None       # (tagged on its first launch)
    # warm again: nothing is stacked twice, no vector uploaded twice
    rows, plan, _l, traced = run(s, sql)
    assert (plan, traced) == ("whole", 0)
    assert _stacks() - stacks0 == _stacks(tid) - of_g0


def test_the_first_launch_of_a_statement_program_says_how_it_picks(six):
    """`slab_pick=index` rides the `launch` span of the first call of a
    statement program built over stacked columns; a one-slab table's
    program indexes nothing and says nothing."""
    _eng, s = six
    s.vars["tidb_tpu_max_slab_rows"] = 8192
    s.execute("CREATE TABLE one (b INT, v BIGINT)")
    s.execute("INSERT INTO one VALUES " + ",".join(
        f"({i % 6}, {i})" for i in range(500)))
    said = {}
    for table in ("one", "g"):
        s.vars["tidb_tpu_max_slab_rows"] = 8192 if table == "one" else SLAB
        sql = f"SELECT b, MAX(v), COUNT(*) FROM {table} GROUP BY b"
        s.query(sql)
        traces = compile_cache.PROGRAM_TRACES
        with timeline.capture() as cap:
            s.query(sql)
        if compile_cache.PROGRAM_TRACES == traces:
            pytest.skip("the statement program was built by an earlier test")
        said[table] = [e["args"].get("slab_pick") for e in cap.events
                       if e["ph"] == "X" and e["cat"] == "launch"]
    assert said == {"one": [None], "g": ["index"]}


def test_a_delta_generation_and_the_one_kept_behind_it_share_the_stacks(six):
    """Six base slabs, a delta slab, liveness masks, and ONE generation
    kept behind the newest: both are read by the same statement program
    over the SAME stacked base arrays (generations of one base build share
    a column's stack by identity; a generation's masks are a stacked array
    of its own), and neither a commit, a warm statement nor the read of the
    kept generation stacks anything again."""
    import datetime
    import time
    eng, s = six
    sql = "SELECT b, COUNT(*), SUM(v) FROM g{asof} GROUP BY b ORDER BY b"
    thrice(s, sql.format(asof=""), slabs=6)
    tid, ent0 = _cached(eng, "g")
    s.execute("INSERT INTO g VALUES (9000, 1, 3, 41, 'c1')")
    s.execute(f"DELETE FROM g WHERE a IN (5, {SLAB + 5}, {5 * SLAB + 5})")
    thrice(s, sql.format(asof=""), first="slabs:spec-miss", slabs=7)
    _tid, ent1 = _cached(eng, "g")
    assert ent1.is_delta and ent1.alive.is_stacked and ent1.delta_cap
    stacks, slices = _stacks(), _slices()
    time.sleep(0.02)
    at = datetime.datetime.fromtimestamp(time.time()).isoformat(sep=" ")
    time.sleep(0.02)
    want_before = oracle(s, sql.format(asof=""))
    s.execute(f"DELETE FROM g WHERE a IN (7, {3 * SLAB + 7}, 9000)")
    rows, plan, launched, traced = run(s, sql.format(asof=""))
    same(rows, oracle(s, sql.format(asof="")), sql)
    assert (plan, len(launched), traced) == ("whole", 1, 0)
    _tid, ent2 = _cached(eng, "g")
    assert ent2 is not ent1 and len(ent2.kept) == 1
    kept = ent2.kept[0]
    # the read one commit behind: the kept generation, the same program
    reads = REGISTRY.counters.get(
        ("tidb_tpu_delta_generation_reads_total", (("age", "kept"),)), 0)
    behind = sql.format(asof=f" AS OF TIMESTAMP '{at}'")
    rows, plan, _l, _t = run(s, behind)     # (its text's first execution)
    same(rows, want_before, sql)
    assert plan == "slabs:spec-miss"
    rows, plan, launched2, traced = run(s, behind)
    same(rows, want_before, sql)
    assert REGISTRY.counters[
        ("tidb_tpu_delta_generation_reads_total",
         (("age", "kept"),))] == reads + 2
    assert (plan, launched2, traced) == ("whole", launched, 0)
    assert _stacks() == stacks, \
        "a commit, a warm statement or a kept generation's read stacked"
    assert _slices() == slices, "… or sliced a slab out of a stack"

    def base_arrays(g):
        return {i: [id(a) for s_, a in c.arrays() if s_ < g.base_slabs]
                for i, c in g.dev.items() if c.is_stacked}
    assert base_arrays(ent2) == base_arrays(kept) and base_arrays(ent2)
    assert all(len(ids) <= 3 for ids in base_arrays(ent2).values())
    # each generation's masks: ONE array of its own
    assert ent2.alive.stack_leaf() is not kept.alive.stack_leaf()
    assert ent2.alive.stack_leaf().shape[0] == 6
    # a stacked array is counted once: by the generation, by the kept
    # bytes (what the older one owns beyond the newest: its masks and its
    # delta slab's arrays), by `information_schema.table_storage`
    seen = {}
    for _s, a in ent2._arrays():
        assert id(a) not in seen
        seen[id(a)] = a
    own = {id(a): a for _s, a in kept._arrays() if id(a) not in seen}
    assert ent2.kept_bytes == sum(a.nbytes for a in own.values())
    assert id(kept.alive.stack_leaf()) in own
    assert ent2.hbm_bytes() == sum(a.nbytes for a in seen.values()) \
        + ent2.kept_bytes
    phys = {r["column"]: r["physical_bytes"]
            for r in dc.storage_stats(id(eng.store)) if r["table_id"] == tid}
    for i, col in ent2.dev.items():
        held = {id(a): a.nbytes for g in (ent2, kept)
                for _s, a in g.dev[i].arrays()}
        assert phys[i] == sum(held.values())


MIXED = {
    # what reads `g` beside the statement program that stacked it
    "whole": "SELECT b, COUNT(*), SUM(v), MIN(a) FROM g GROUP BY b",
    "whole-tree": "SELECT d.name, COUNT(*), SUM(g.v), SUM(d.w) " + _GJOIN
                  + "GROUP BY d.name ORDER BY d.name",
    "pairs": "SELECT b, COUNT(DISTINCT v) FROM g GROUP BY b ORDER BY b",
    "runs": "SELECT v, COUNT(*), SUM(a) FROM g GROUP BY v "
            "ORDER BY SUM(a) DESC, v LIMIT 5",
    "filter-root": "SELECT a, v FROM g WHERE v = 100 AND b = 3",
    "order-root": "SELECT a, v FROM g ORDER BY v DESC, a LIMIT 4",
    "join-rows": "SELECT g.a, d.name FROM g JOIN d ON g.b = d.id "
                 "WHERE g.v = 110 ORDER BY g.a LIMIT 6",
    "build-side": "SELECT COUNT(*), SUM(g.v) FROM f JOIN g ON f.a = g.a "
                  "WHERE f.b < 4",
}


def test_a_warm_window_of_mixed_plans_slices_no_slab(six, monkeypatch):
    """One server runs, over the SAME stacked table, a statement program
    (chain and join tree), the per-slab plans (`slabs:pairs`, `slabs:runs`),
    filter and ORDER BY roots, a join's row root and a statement that reads
    it whole as a build side. Warm, none of them copies a slab out of the
    stacks (`tidb_tpu_slab_slices_total` stands still: the per-slab
    programs index the stack inside their traces, `SlabColumn.at`; a table
    read whole lists its slabs there, `SlabColumn.whole`), nothing is
    stacked again, and every answer is the host's."""
    _eng, s = six
    monkeypatch.setattr(tree_fragment, "SLOT_ADDRESS_CAP", 64)
    want = {k: oracle(s, sql) for k, sql in MIXED.items()}
    plans = {}
    for _ in range(3):
        for k, sql in MIXED.items():
            rows, plans[k], _l, _t = run(s, sql)
            same(rows, want[k], sql)
    assert plans["whole"] == plans["whole-tree"] == "whole"
    assert (plans["pairs"], plans["runs"]) == ("slabs:pairs", "slabs:runs")
    _tid, ent = _cached(_eng, "g")
    assert all(c.is_stacked for c in ent.dev.values())
    slices, stacks = _slices(), _stacks()
    for k, sql in MIXED.items():
        rows, plan, _l, traced = run(s, sql)
        same(rows, want[k], sql)
        assert (plan, traced) == (plans[k], 0), k
        assert _slices() == slices, f"{k} sliced a slab out of a stack"
    assert _stacks() == stacks


@pytest.mark.parametrize("n", [77, 1000, 1024])
def test_a_stack_gives_back_every_slab_whatever_its_length(n):
    """A leaf's rows lie folded to whole lanes of 128 in its column's
    stack — padded where the slab's length is no multiple — and every way
    of reading a slab gives the slab back: `col[s]` (a slice, counted),
    `at(s)` and `whole()` resolved inside a trace, a turn of a statement
    program's loop; a hole stays a hole, the delta slab its own arrays."""
    from tidb_tpu.ops.jax_env import jax, jnp
    slabs = [(jnp.arange(n, dtype=jnp.int64) + s * n,
              jnp.arange(n) % 3 == s % 3) for s in range(4)]
    slabs[2] = None                             # (pruned at first touch)
    delta = (jnp.zeros(16, jnp.int64), jnp.zeros(16, bool))
    col = dc.SlabColumn(slabs, delta)
    col.stack("t")
    assert col.is_stacked and col.holes() == {2} and len(col) == 5
    (values, _mask) = [a for _s, a in col.arrays()][:2]
    assert values.shape == (3, -(-n // 128), 128)
    slices = _slices()

    def same_slab(got, s):
        if slabs[s] is None:
            return got is None
        return all(bool((g == w).all()) and g.shape == w.shape
                   for g, w in zip(got, slabs[s]))
    assert all(same_slab(col[s], s) for s in range(4))
    assert _slices() - slices == 3 and col[4] is delta
    here = jax.jit(dc.in_place)
    assert all(same_slab(here(col.at(s)), s) for s in range(4))
    whole = here(col.whole())
    assert all(same_slab(whole[s], s) for s in range(4)) \
        and len(whole) == 5
    picks = (jnp.asarray(col.rows_of((3, 0)), jnp.int32),)
    pick = jax.jit(dc.in_place, static_argnums=2)
    assert same_slab(pick(col.stacked(), picks, 0), 3)
    assert same_slab(pick(col.stacked(), picks, 1), 0)
    assert _slices() - slices == 3              # (only `col[s]` copies)


def test_a_stacked_mask_is_rewritten_by_row_position_in_the_base():
    """The tombstone rewrite of a generation's stacked masks addresses a
    row by its position in the whole base, whatever the fold's padding."""
    from tidb_tpu.executor import device_emit
    from tidb_tpu.ops.jax_env import jnp
    import numpy as np
    cap = 1000                                  # (no multiple of 128)
    col = dc.SlabColumn(jnp.ones(cap, bool) for _ in range(3))
    col.stack("t")
    dead = np.array([5, cap + 5, 3 * cap - 1, 3 * cap, 3 * cap],
                    dtype=np.int32)             # (padded with the size)
    col.set_stack(device_emit.emit_alive_update(
        col.stack_leaf(), np.empty(0, np.int32), dead, cap, stacked=True))
    got = np.concatenate([np.asarray(col[s]) for s in range(3)])
    want = np.ones(3 * cap, bool)
    want[[5, cap + 5, 3 * cap - 1]] = False
    assert (got == want).all()
    # (a stacked base is rewritten whole, never a slab at a time)
    with pytest.raises(TypeError, match="set_stack"):
        col[1] = jnp.zeros(cap, bool)


def test_masks_are_born_stacked():
    """A table's first commit makes its liveness masks on the device from
    the live prefixes: ONE array where the base slabs can be held so
    (several, on one device, none lost) — one program: no fill, no
    counter, nothing through the host — whether or not its columns are
    stacked yet, so that the masks and the program that rewrites them have
    one form for the table's life; a mask a slab where slabs have owners.
    The same rows either way, whatever the fold's padding."""
    import types
    import numpy as np
    from tidb_tpu.executor import delta
    from tidb_tpu.ops.jax_env import jnp
    cap, rows = 1000, (1000, 1000, 17)
    col = dc.SlabColumn((jnp.zeros(cap, jnp.int32),) for _ in rows)
    ent = types.SimpleNamespace(
        slab_cap=cap, base_slabs=3, owners=None, lost=set(), device=0,
        dev={0: col}, slab_rows=lambda s: rows[s])
    stacks, slices = _stacks(), _slices()
    born = delta.base_masks(ent, "t")
    assert not col.is_stacked
    assert born.is_stacked and born.n_base == 3 and not born.holes()
    assert born.stack_leaf().shape == (3, 8, 128)
    assert _stacks() == stacks, "a commit filled a stack"
    ent.owners = (0, 0, 0)
    listed = delta.base_masks(ent, "t")
    assert not listed.is_stacked and listed.n_base == 3
    for s, n in enumerate(rows):
        want = np.arange(cap) < n
        assert (np.asarray(born[s]) == want).all()
        assert (np.asarray(listed[s]) == want).all()
    assert _slices() == slices + 3                  # (only `col[s]` copies)


def test_slabs_on_several_devices_or_with_a_lost_slab_are_never_stacked():
    """`SlabPicks.of` — the one place a column is stacked — leaves an
    entry whose slabs have owners, or that lost one, its lists: a lost
    slab's refill writes a slab, and several devices hold no one array."""
    import types
    from tidb_tpu.ops.jax_env import jnp
    for owners, lost in (((0, 1), set()), (None, {1})):
        ent = types.SimpleNamespace(owners=owners, lost=lost)
        col = dc.SlabColumn((jnp.zeros(8),) for _ in range(2))
        with pytest.raises(AssertionError, match="stack"):
            dc.SlabPicks(ent, (0, 1), "t").of(col)
        assert not col.is_stacked
        col[1] = (jnp.ones(8),)                 # (the refill's write)
    ent = types.SimpleNamespace(owners=None, lost=None)
    dc.SlabPicks(ent, (0, 1), "t").of(col)
    assert col.is_stacked
