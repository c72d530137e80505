"""Large-group statements — TPC-H's Q3, Q10 and Q18 as specified (a GROUP BY
over an entity key, ORDER BY an aggregate, LIMIT n; Q18's HAVING subquery) —
device-resident end to end, against a reference that shares nothing with
the program (`benchmarks/datasets/tpch_largegroup.py`, plain numpy).

Pinned here, at a small scale on the CPU backend with the device path
forced:

* ORDER BY a wide-DECIMAL aggregate is numeric (`rank_keys` ranked such
  values as text: 9976.4316 above 495455.7871), on the host executors and
  on the device finalize alike;
* the order root fuses through the select list's projection, the top-n runs
  in the finalize program, and the host receives n rows (`host_rows`,
  `D2H_BYTES`);
* an uncorrelated `IN (SELECT k … GROUP BY k HAVING …)` over a device-sized
  scan plans as a semijoin on a nested device-rows fragment and nothing
  runs at plan time; small ones keep the eager path;
* `tidb_tpu_strict = on` raises for a host join/aggregate/sort over a
  device-sized scan;
* grouping by sorted runs (many groups, bounded keys) and direct slot
  addressing (few) give the same rows; a wrong group estimate climbs the
  capacity ladder to the same rows; six slabs and one slab give the same
  rows.
"""

import importlib.util
from pathlib import Path

import numpy as np
import pytest

from tidb_tpu.errors import ExecutionError
from tidb_tpu.executor import agg_slabs, compile_cache, tree_fragment
from tidb_tpu.session import Engine
from tidb_tpu.util import timeline
from tidb_tpu.util.observability import REGISTRY, normalize_sql

ROOT = Path(__file__).resolve().parents[1]
SCALE = 0.01


def _dataset():
    spec = importlib.util.spec_from_file_location(
        "tests_tpch_largegroup",
        ROOT / "benchmarks" / "datasets" / "tpch_largegroup.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


LG = _dataset()


def planted(seed: int) -> dict:
    """The generator's data with what a top-n comparison must survive:
    revenues of different digit counts (a 0.01 price beside the greatest),
    negative sums (a discount over 1.00), and ties across the LIMIT
    boundary of Q3 (12 orders with one identical qualifying lineitem each)
    and of Q18 (150 orders of 8 × 50.00, three pairs with equal
    o_totalprice and o_orderdate)."""
    data = LG.generate(SCALE, seed)
    li, orders, cust = data["lineitem"], data["orders"], data["customer"]
    rng = np.random.default_rng([seed, 99])
    n, n_orders = len(li["l_orderkey"]), len(orders["o_orderkey"])
    cut = LG.base.days("1995-03-15")
    building = int(np.flatnonzero(
        cust["c_mktsegment"] == LG.base.SEGMENTS.index("BUILDING"))[0])
    # digit counts and signs
    li["l_extendedprice"][rng.choice(n, 40, replace=False)] = 1
    li["l_discount"][rng.choice(n, 60, replace=False)] = 150
    # Q3: twelve orders tie at the greatest revenue
    tied = rng.choice(n_orders, 12, replace=False)
    li["l_shipdate"][np.isin(li["l_orderkey"], tied)] = cut - 100
    rows = rng.choice(np.flatnonzero(~np.isin(li["l_orderkey"], tied)), 12,
                      replace=False)
    li["l_orderkey"][rows] = tied
    li["l_shipdate"][rows] = cut + 5
    li["l_extendedprice"][rows] = 10_500_000
    li["l_discount"][rows] = 0
    orders["o_orderdate"][tied] = cut - 5
    orders["o_custkey"][tied] = building
    # Q18: 150 orders over the quantity, ties on price and date
    big = rng.choice(np.setdiff1d(np.arange(n_orders), tied), 150,
                     replace=False)
    rows = rng.choice(np.flatnonzero(~np.isin(li["l_orderkey"], tied)),
                      150 * 8, replace=False)
    li["l_orderkey"][rows] = np.repeat(big, 8)
    li["l_quantity"][rows] = 5000
    for a, b in big[:6].reshape(3, 2):
        orders["o_totalprice"][b] = orders["o_totalprice"][a]
        orders["o_orderdate"][b] = orders["o_orderdate"][a]
    return data


def device_session(eng, **settings):
    s = eng.new_session()
    s.vars.update(tidb_tpu_engine="on", tidb_tpu_row_threshold=1,
                  tidb_tpu_strict="on", **settings)
    return s


def text_rows(rs):
    return [tuple(str(x) for x in row) for row in rs.rows]


def ledger(s, sql):
    rs = s.execute("SELECT * FROM information_schema.statements_summary")[0]
    want = normalize_sql(sql)
    return next(dict(zip(rs.names, r)) for r in rs.rows
                if dict(zip(rs.names, r))["DIGEST_TEXT"] == want)


def counter(name, **labels):
    return sum(v for n, lab, v in REGISTRY.metric_rows()
               if n == name and all(f"{k}={w}" in lab
                                    for k, w in labels.items()))


@pytest.fixture(scope="module", params=[3, 17, 2147483659])
def loaded(request):
    data = planted(request.param)
    eng = Engine()
    eng.global_vars["tidb_enable_auto_analyze"] = False
    LG.load(eng, data)
    yield eng, LG.reference(data)
    eng.close()


@pytest.fixture
def sorted_runs(monkeypatch):
    """Key domains of this scale address slots directly; a domain cap this
    low sends every grouped aggregate through sorted runs, as the full
    scale's millions of keys do."""
    monkeypatch.setattr(tree_fragment, "SLOT_ADDRESS_CAP", 64)
    compile_cache._COMPILE_CACHE.clear()
    agg_slabs._SPEC_CACHE.clear()
    yield
    compile_cache._COMPILE_CACHE.clear()
    agg_slabs._SPEC_CACHE.clear()


@pytest.mark.parametrize("name", ["Q3", "Q10", "Q18"])
def test_statement_equals_the_plain_reference_by_sorted_runs(
        loaded, sorted_runs, name):
    eng, ref = loaded
    s = device_session(eng, tidb_tpu_max_slab_rows=16384)
    fb = counter("tidb_tpu_device_fallbacks_total")
    got = text_rows(s.execute(LG.STATEMENTS[name])[0])
    assert got == ref[name]
    assert len(got) == min(LG.LIMITS[name], len(got))
    assert s.last_engine == "tpu"
    assert counter("tidb_tpu_device_fallbacks_total") == fb
    # warm: the same rows, no capacity-ladder retry, nothing compiled
    retries = counter("tidb_tpu_ladder_retries_total")
    assert text_rows(s.execute(LG.STATEMENTS[name])[0]) == ref[name]
    assert counter("tidb_tpu_ladder_retries_total") == retries


def _traced(s, sql):
    """Run `sql` once under the span recorder → (rows as text, the
    `launch` spans in order as (program name, its `run_sums` tag))."""
    with timeline.capture() as cap:
        rows = text_rows(s.execute(sql)[0])
    return rows, [(e["name"], e["args"].get("run_sums"))
                  for e in sorted((e for e in cap.events if e["ph"] == "X"
                                   and e["cat"] == "launch"),
                                  key=lambda e: e["ts"])]


def _scans() -> dict:
    return {dict(labels)["range"]: v
            for (name, labels), v in REGISTRY.counters.items()
            if name == "tidb_tpu_run_sum_scans_total"}


# statement → the `run_sums` tags of its traced finalizes, <words scanned>/
# <state arrays>. Q18's SUM(l_quantity) holds 13 bits, so the three limbs
# and the count of its wide SUM are ONE word, in the nested aggregate and
# in the outer one (whose width comes through the join tree). Q3's and
# Q10's revenue is a wide SUM of FOUR limb planes (its argument's type is
# wide) and a count: with the generator's data (0 ≤ discount ≤ 0.10) its
# 33 bits are two limb fields, which with their rows' growth and the count
# fill two words, here as at the benchmark's scale; the PLANTED data holds
# a discount over 1.00, the revenue's range reaches below zero and it has
# no width: three limbs and the count a word each (limb 2 signed, at whole
# width), as the parent scanned them.
RUN_SUMS = {
    "planted": {"Q3": ["4/5"], "Q10": ["4/5"], "Q18": ["1/4", "1/4"]},
    "generated": {"Q3": ["2/5"], "Q10": ["2/5"], "Q18": ["1/4", "1/4"]},
}


@pytest.mark.parametrize("name", ["Q3", "Q10", "Q18"])
@pytest.mark.parametrize("data", sorted(RUN_SUMS))
def test_the_traced_finalizes_say_how_many_words_they_scan(sorted_runs,
                                                           data, name):
    """The first execution traces the statement's finalize(s), the second
    the same at the capacity the first settled (`_tight_cap`): tag
    `run_sums` on the `launch` span that traced it — on a finalize and on
    nothing else — and the scans counted by whether their fields had
    known widths; the rows are the reference's; a warm execution says
    nothing."""
    made = planted(3) if data == "planted" else LG.generate(SCALE, 3)
    eng = Engine()
    eng.global_vars["tidb_enable_auto_analyze"] = False
    LG.load(eng, made)
    try:
        s = device_session(eng, tidb_tpu_max_slab_rows=16384)
        want = RUN_SUMS[data][name]
        before, said = _scans(), []
        for _execution in range(2):
            rows, launches = _traced(s, LG.STATEMENTS[name])
            assert rows == LG.reference(made)[name]
            assert not [t for prog, t in launches
                        if t and not prog.startswith("finalize_")]
            said += [t for _prog, t in launches if t]
        assert said[:len(want)] == want and set(said) == set(want), said
        grew = {k: v - before.get(k, 0) for k, v in _scans().items()}
        assert sum(grew.values()) == sum(int(t.partition("/")[0])
                                         for t in said), grew
        # the signed limb of a revenue that may be negative, and no other
        assert grew.get("whole", 0) == said.count("4/5")
        before = _scans()
        rows, launches = _traced(s, LG.STATEMENTS[name])
        assert rows == LG.reference(made)[name] and _scans() == before
        assert not [t for _prog, t in launches if t]
    finally:
        eng.close()


WIDENED = ("SELECT k, SUM(q), AVG(q), COUNT(*) FROM w GROUP BY k "
           "ORDER BY SUM(q) DESC, k LIMIT 5")


def test_an_append_past_a_power_of_two_mints_one_finalize(sorted_runs):
    """`q` holds 13 bits when the finalize is first traced. A quantity of
    2²⁰ appended through SQL widens the cached bounds (a sorted-runs
    statement reads a plain rebuild over a delta generation, gate
    `consumer`): the next execution traces ONE new finalize — the slab
    program and the shared sort keep their names, the widths are in no
    signature of theirs — and answers exactly; a later value inside the
    widened bounds mints nothing. (Compression off: a column's LAYOUT
    follows its values too, and a new layout is a new slab program for a
    reason that is not this one.)"""
    eng = Engine()
    eng.global_vars["tidb_enable_auto_analyze"] = False
    s = eng.new_session()
    s.execute("CREATE TABLE w (k BIGINT, q BIGINT)")
    s.execute("INSERT INTO w VALUES " + ",".join(
        f"({(i * 37) % 1000}, {(i * 7919) % 5000})" for i in range(3000)))
    s.execute("ANALYZE TABLE w")
    host = eng.new_session()
    host.vars["tidb_tpu_engine"] = "off"
    dev = device_session(eng, tidb_tpu_compression="off",
                         tidb_tpu_compaction="off")

    def run():
        rows, launches = _traced(dev, WIDENED)
        assert dev.last_engine == "tpu"
        assert rows == text_rows(host.execute(WIDENED)[0])
        return launches

    try:
        first = run()
        slab, sort, fin = [prog for prog, _t in first]
        assert slab.startswith("partial_chain_") \
            and sort.startswith("sort_rows_") and fin.startswith("finalize_")
        # SUM(q) and AVG(q) share q's 13-bit field, all three the count:
        # seven state arrays, one word
        assert [t for _p, t in first] == [None, None, "1/7"]
        assert run() == [(slab, None), (sort, None), (fin, None)]
        traces = compile_cache.PROGRAM_TRACES
        dev.execute(f"INSERT INTO w VALUES (3, {2 ** 20})")
        wider = run()
        assert [p for p, _t in wider[:2]] == [slab, sort]
        assert wider[2][0].startswith("finalize_") and wider[2][0] != fin
        assert wider[2][1] == "1/7"         # 21 + 12 and 1 + 12 bits
        assert compile_cache.PROGRAM_TRACES == traces + 1
        assert text_rows(dev.execute(WIDENED)[0])[0][:2] == \
            ("3", str(2 ** 20 + sum((i * 7919) % 5000 for i in range(3000)
                                    if (i * 37) % 1000 == 3)))
        dev.execute(f"INSERT INTO w VALUES (4, {2 ** 20 - 7})")
        assert run() == [(slab, None), (sort, None), (wider[2][0], None)]
        assert compile_cache.PROGRAM_TRACES == traces + 1
    finally:
        eng.close()


def test_sorted_runs_carry_the_widths_and_the_slab_programs_do_not():
    """`KeyBounds(RUNS)` keeps `arg_bits` (the finalize packs its scans by
    them), and the bounds' part of a slab program's signature — which the
    shared sort's and the statement's `aggrows` names follow — reads as
    the parent's: the mode and the key bounds, no width."""
    from tidb_tpu.ops import factorize as F
    bounds = [(1, 12_000_000)]
    kb = F.choose_key_bounds(bounds, 12_000_001, 1024, 1 << 22, True,
                             (13, None))
    assert kb == F.KeyBounds(F.RUNS, ((1, 12_000_000),), (13, None))
    assert F.bounds_sig(kb) == "runs[(1, 12000000)]" \
        == F.bounds_sig(F.KeyBounds(F.RUNS, ((1, 12_000_000),)))
    assert F.widths_sig(kb) == "|ab=13,-"
    # no width known: nothing rides and nothing is appended
    bare = F.choose_key_bounds(bounds, 12_000_001, 1024, 1 << 22, True,
                               (None, None))
    assert bare.arg_bits == () and F.widths_sig(bare) == ""
    # the other lowerings' signatures read as they did
    slots = F.choose_key_bounds([(0, 5)], 7, 1024, 1 << 22, True, (13,))
    assert F.bounds_sig(slots) == "[(0, 5)]|ab=13"
    assert F.bounds_sig(F.KeyBounds(F.FACTORIZE, (), (13,))) \
        == "factorize[]|ab=13"


@pytest.mark.parametrize("name", ["Q3", "Q10", "Q18"])
def test_statement_equals_the_plain_reference_by_slot_addressing(loaded,
                                                                 name):
    eng, ref = loaded
    s = device_session(eng)
    assert text_rows(s.execute(LG.STATEMENTS[name])[0]) == ref[name]
    assert s.last_engine == "tpu"


def test_the_float32_control_fails_q3(loaded):
    """The step below exact DECIMAL in which the answers change: sums kept
    in a float32 (a float64 holds these sums exactly)."""
    _eng, ref = loaded
    # (the fixture's reference is of the planted data; the control is
    # judged on the same)
    data = planted(3)
    exact = LG.reference(data)
    assert LG.reference(data, arithmetic="float32")["Q3"] != exact["Q3"]
    assert LG.reference(data, arithmetic="float64") == exact


@pytest.mark.parametrize("name", ["Q3", "Q10", "Q18"])
def test_the_host_receives_the_result_rows_and_nothing_per_group(
        loaded, sorted_runs, name):
    """The fusion cannot silently come apart: the plan is one fragment
    under a projection, the host executors consume the LIMIT's rows, and
    the fetch holds the result and the ladder's control values."""
    eng, ref = loaded
    s = device_session(eng, tidb_tpu_max_slab_rows=16384)
    sql = LG.STATEMENTS[name]
    ops = [str(r[0]).lstrip(" └─") for r in s.execute("EXPLAIN " + sql)[0].rows]
    assert ops[:3] == ["Projection", "TpuFragment", "TopN"], ops
    assert "IndexScan" not in ops and ops.count("HashAgg") == \
        (2 if name == "Q18" else 1)
    s.execute(sql)
    d2h0 = float(ledger(s, sql)["D2H_BYTES"])
    host0 = counter("tidb_tpu_host_rows_total")
    s.execute(sql)
    assert counter("tidb_tpu_host_rows_total") - host0 == len(ref[name])
    assert float(ledger(s, sql)["D2H_BYTES"]) - d2h0 <= 65536
    assert ledger(s, sql)["ENGINE"] == "tpu"


def test_q18_plans_a_semijoin_on_a_nested_fragment_and_runs_nothing_early(
        loaded):
    eng, _ref = loaded
    s = device_session(eng)
    before = s._subq_execs
    lines = [" ".join(str(c) for c in r)
             for r in s.execute("EXPLAIN " + LG.Q18)[0].rows]
    assert s._subq_execs == before, "the subquery ran at plan time"
    assert any("semi join" in ln for ln in lines)
    assert any("TpuFragment" in ln and "rows:device" in ln for ln in lines)


def test_a_small_in_subquery_keeps_the_eager_path(loaded):
    eng, _ref = loaded
    s = eng.new_session()
    s.vars.update(tidb_tpu_engine="on", tidb_tpu_row_threshold=10 ** 9)
    before = s._subq_execs
    s.execute("EXPLAIN SELECT COUNT(*) FROM orders WHERE o_orderkey IN "
              "(SELECT l_orderkey FROM lineitem GROUP BY l_orderkey "
              "HAVING SUM(l_quantity) > 300)")
    assert s._subq_execs == before + 1


def test_strict_raises_on_a_host_join_over_a_device_sized_scan(loaded):
    """A non-equi join has no device lowering: its scans stay on the host.
    Under strict that is an error and a counted fallback; without strict
    the statement runs; a scan that only returns rows never raises."""
    eng, _ref = loaded
    sql = ("SELECT COUNT(*) FROM customer JOIN orders "
           "ON c_custkey < o_custkey - 1000000")
    s = device_session(eng)
    fb = counter("tidb_tpu_device_fallbacks_total", reason="shape")
    with pytest.raises(ExecutionError, match="tidb_tpu_strict.*on the host"):
        s.execute(sql)
    assert counter("tidb_tpu_device_fallbacks_total", reason="shape") \
        == fb + 1
    s.vars["tidb_tpu_strict"] = "off"
    assert s.execute(sql)[0].rows == [(0,)]
    s.vars["tidb_tpu_strict"] = "on"
    assert len(s.execute("SELECT c_custkey FROM customer")[0].rows) == \
        len(s.execute("SELECT * FROM customer LIMIT 5000")[0].rows)


def test_a_wrong_group_estimate_climbs_the_ladder_to_the_same_rows(
        loaded, sorted_runs, monkeypatch):
    eng, ref = loaded
    monkeypatch.setattr(agg_slabs, "initial_group_cap",
                        lambda root, default, max_cap, key_bounds: 16)
    s = device_session(eng, tidb_tpu_max_slab_rows=16384)
    retries = counter("tidb_tpu_ladder_retries_total", rung="group")
    assert text_rows(s.execute(LG.Q10)[0]) == ref["Q10"]
    assert counter("tidb_tpu_ladder_retries_total", rung="group") > retries
    # the specialization cache adopted the settled capacity
    retries = counter("tidb_tpu_ladder_retries_total")
    assert text_rows(s.execute(LG.Q10)[0]) == ref["Q10"]
    assert counter("tidb_tpu_ladder_retries_total") == retries


@pytest.mark.parametrize("name", ["Q3", "Q18"])
def test_six_slabs_and_one_slab_give_the_same_rows(loaded, sorted_runs,
                                                   name):
    eng, ref = loaded
    one = device_session(eng)
    six = device_session(eng, tidb_tpu_max_slab_rows=16384)
    assert text_rows(six.execute(LG.STATEMENTS[name])[0]) == \
        text_rows(one.execute(LG.STATEMENTS[name])[0]) == ref[name]
    assert float(ledger(six, LG.STATEMENTS[name])["PROGRAMS_LAUNCHED"]) > 0


# ---------------------------------------------------------------------------
# ordering by value
# ---------------------------------------------------------------------------

def test_rank_keys_orders_wide_decimals_by_value():
    from tidb_tpu import types as T
    from tidb_tpu.chunk import Chunk, Column
    from tidb_tpu.executor.sort import rank_keys
    from tidb_tpu.expression import ColumnRef
    ft = T.decimal(53, 4)
    vals = np.array([99764316, 4954557871, -125000, 0, 4954557871,
                     10 ** 30, -10 ** 30], dtype=object)
    valid = np.array([True, True, True, False, True, True, True])
    chunk = Chunk([Column(ft, vals, valid)])
    ref = ColumnRef(0, ft, "r")
    (asc,) = rank_keys([ref], [False], chunk)
    (desc,) = rank_keys([ref], [True], chunk)
    # ascending: NULL first, then by VALUE; equal values share a rank
    assert list(np.argsort(asc, kind="stable")) == [3, 6, 2, 0, 1, 4, 5]
    assert asc[1] == asc[4]
    # descending: greatest first, NULL last
    assert list(np.argsort(desc, kind="stable")) == [5, 1, 4, 0, 2, 6, 3]


@pytest.mark.parametrize("engine", ["on", "off"])
def test_order_by_a_wide_decimal_sum_is_numeric_on_both_engines(engine):
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE t (k BIGINT, a DECIMAL(15,2), b DECIMAL(15,2))")
    s.execute("INSERT INTO t VALUES (1,'9976.43','0.5'), (2,'495455.78','0.5'),"
              " (3,'-12.50','0'), (4,NULL,'1'), (2,'10.00','0.1'),"
              " (5,'99.99','2'), (6,'9976.43','0.5')")
    s.execute("ANALYZE TABLE t")
    s.vars.update(tidb_tpu_engine=engine, tidb_tpu_row_threshold=1)
    rows = s.execute("SELECT k, SUM(a * (1 - b)) AS r FROM t GROUP BY k "
                     "ORDER BY r DESC, k LIMIT 5")[0].rows
    assert [r[0] for r in rows] == [2, 1, 6, 3, 5]
    assert s.last_engine == ("tpu" if engine == "on" else "cpu")
    rows = s.execute("SELECT k, SUM(a * (1 - b)) AS r FROM t GROUP BY k "
                     "ORDER BY r, k")[0].rows
    assert [r[0] for r in rows] == [4, 5, 3, 1, 6, 2]
    eng.close()


@pytest.mark.parametrize("ftype", ["DOUBLE", "FLOAT"])
def test_float_sums_over_many_bounded_groups_match_the_cpu_engine(ftype):
    """A floating-point SUM is no difference of two prefix sums: three
    groups near 1e16 beside thousands near 1 would cancel to 0 or 8. Over
    more than SLOT_ADDRESS_CAP bounded groups a float argument keeps the
    slot lowering, and SUM, AVG and the top-n by the sum are the CPU
    engine's."""
    rng = np.random.default_rng(7)
    n, groups = 4000, 3001
    k = rng.integers(0, groups, n)
    k[:groups] = np.arange(groups)
    x = rng.uniform(0.1, 2.0, n)
    x[rng.choice(n, 3, replace=False)] = [1e16, -3e16, 7e15]
    eng = Engine()
    eng.global_vars["tidb_enable_auto_analyze"] = False
    s = eng.new_session()
    s.execute(f"CREATE TABLE t (id BIGINT PRIMARY KEY, k BIGINT, x {ftype})")
    for lo in range(0, n, 1000):
        s.execute("INSERT INTO t VALUES " + ", ".join(
            f"({i}, {int(k[i])}, {float(x[i])!r})" for i in range(lo, lo + 1000)))
    s.execute("ANALYZE TABLE t")
    grouped = "SELECT k, SUM(x) AS sx, AVG(x), COUNT(*) FROM t GROUP BY k"
    top = grouped + " ORDER BY sx DESC, k LIMIT 20"
    cpu = eng.new_session()
    cpu.vars.update(tidb_tpu_engine="off")
    dev = device_session(eng)
    for sql in (grouped + " ORDER BY k", top):
        want = cpu.execute(sql)[0].rows
        got = dev.execute(sql)[0].rows
        assert dev.last_engine == "tpu"
        assert [r[0] for r in got] == [r[0] for r in want]
        for g, w in zip(got, want):
            assert g[3] == w[3]
            assert g[1] == pytest.approx(w[1], rel=1e-6), (g, w)
            assert g[2] == pytest.approx(w[2], rel=1e-6), (g, w)
    eng.close()
