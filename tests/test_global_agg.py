"""An aggregate without GROUP BY has one group: every driver gives its
partial ONE slot (`agg_slabs.initial_group_cap`), whose states are plain
masked reductions (`ops/segment.py`), and says so — `grouping="global"`,
`gcap=1` on the `device.fragment` span, `tidb_tpu_agg_partials_total
{grouping="global"}` per partial launched. Device against the CPU oracle,
over every aggregate kind and every path a global aggregate can take."""

import numpy as np
import pytest

from tidb_tpu.executor import device_cache as dc
from tidb_tpu.executor import agg_slabs, compile_cache
from tidb_tpu.session import Engine
from tidb_tpu.util import timeline
from tidb_tpu.util.observability import REGISTRY

N_ROWS = 5000


def _load(s, name="g", n=N_ROWS, seed=11):
    s.execute(f"CREATE TABLE {name} (a BIGINT, b DOUBLE, n DECIMAL(10,2), "
              "w DECIMAL(30,4), c VARCHAR(8), k BIGINT, z BIGINT)")
    rng = np.random.default_rng(seed)
    for base in range(0, n, 1000):
        rows = []
        for i in range(base, min(base + 1000, n)):
            b = "NULL" if i % 97 == 0 else repr(float(rng.normal()))
            nn = "NULL" if i % 89 == 0 else \
                f"{rng.integers(-50000, 50000) / 100:.2f}"
            w = f"{int(rng.integers(10 ** 17, 10 ** 18))}" \
                f"{int(rng.integers(0, 10 ** 6)):06d}.{i % 10000:04d}"
            rows.append(f"({i}, {b}, {nn}, {w}, 'c{i % 6}', {i % 40}, NULL)")
        s.execute(f"INSERT INTO {name} VALUES " + ",".join(rows))


@pytest.fixture(scope="module")
def session():
    eng = Engine()
    eng.global_vars["tidb_enable_auto_analyze"] = False
    s = eng.new_session()
    _load(s)
    s.execute("CREATE TABLE dim (k BIGINT, v BIGINT)")
    s.execute("INSERT INTO dim VALUES " +
              ",".join(f"({i}, {i * 3})" for i in range(30)))
    s.execute("CREATE TABLE h (a BIGINT, k BIGINT, c VARCHAR(8))")
    s.execute("INSERT INTO h SELECT a, k, c FROM g")
    s.execute("ANALYZE TABLE g")
    s.execute("ANALYZE TABLE dim")
    yield s
    eng.close()


def _partials(grouping: str) -> float:
    return REGISTRY.counters.get(
        ("tidb_tpu_agg_partials_total", (("grouping", grouping),)), 0)


def run_checked(s, sql, tmp_path, monkeypatch, **vars_):
    """`sql` on the device path under `vars_` → (rows, partials launched).
    Checks what every case checks: the rows equal the CPU oracle's, every
    program the statement asked for has one group slot, the spans say
    `grouping="global"` with `gcap=1` and nothing else, and the counter
    counted the launches under "global" alone."""
    s.vars["tidb_tpu_engine"] = "off"
    oracle = s.query(sql).rows
    asked = []
    real = compile_cache.get_or_build

    def recording(sig, kind, build):
        prog = real(sig, kind, build)
        asked.append((kind, getattr(prog, "group_cap", None)))
        return prog

    monkeypatch.setattr(compile_cache, "get_or_build", recording)
    before = {g: _partials(g) for g in ("global", "bounds", "factorize")}
    s.vars.update(tidb_tpu_engine="on", tidb_tpu_row_threshold=1,
                  tidb_tpu_strict="on", **vars_)
    timeline.start_global(str(tmp_path))
    try:
        rows = s.query(sql).rows
        assert s.last_engine == "tpu"
    finally:
        timeline.stop_global()
        monkeypatch.undo()
        for v in ("tidb_tpu_strict", *vars_):
            s.vars.pop(v, None)
        s.vars["tidb_tpu_engine"] = "off"
    assert len(rows) == len(oracle) == 1
    for got, want in zip(rows[0], oracle[0]):
        if isinstance(want, float):
            assert got == pytest.approx(want, rel=1e-9, abs=1e-12), sql
        else:
            assert got == want, (sql, rows, oracle)
    caps = [cap for _kind, cap in asked if cap is not None]
    assert caps and set(caps) == {1}, asked
    tags = [(e["name"], e["args"]["grouping"], e["args"]["gcap"])
            for e in timeline.last_events()
            if e["ph"] == "X" and "grouping" in e["args"]]
    assert tags and {t[1:] for t in tags} == {("global", 1)}, tags
    grew = {g: _partials(g) - before[g] for g in before}
    assert grew["bounds"] == grew["factorize"] == 0, grew
    launched = sum(1 for e in timeline.last_events()
                   if e["ph"] == "X" and e["cat"] == "launch")
    assert 1 <= grew["global"] <= launched, (grew, launched)
    return rows, grew["global"]


# one statement per aggregate kind; `a % 11 = 12` selects nothing and no
# zone map can tell, so the partials launch over an empty selection
SHAPES = {
    "count_star": "SELECT COUNT(*) FROM g WHERE a >= 100",
    "count_col": "SELECT COUNT(b), COUNT(n), COUNT(c) FROM g",
    "sum_narrow_decimal": "SELECT SUM(n), SUM(n * n) FROM g WHERE k < 30",
    "sum_wide_decimal": "SELECT SUM(w), COUNT(*) FROM g",
    "sum_double": "SELECT SUM(b), SUM(b * 2) FROM g WHERE a % 3 = 0",
    "avg": "SELECT AVG(n), AVG(b), AVG(a) FROM g",
    "min_max": "SELECT MIN(a), MAX(a), MIN(b), MAX(n), MAX(k) FROM g "
               "WHERE a > 7",
    "variance": "SELECT VAR_POP(b), VAR_SAMP(b), STDDEV(b), "
                "STDDEV_SAMP(a) FROM g",
    "distinct": "SELECT SUM(DISTINCT k), COUNT(DISTINCT k, c), "
                "COUNT(DISTINCT c) FROM g",
    "empty_selection": "SELECT COUNT(*), SUM(n), MIN(a), AVG(b) FROM g "
                       "WHERE a % 11 = 12",
    "all_null_column": "SELECT COUNT(z), SUM(z), MIN(z), AVG(z), COUNT(*) "
                       "FROM g",
    "q6_shaped": "SELECT COUNT(*), SUM(n * n) FROM g WHERE a >= 500 "
                 "AND a < 4000 AND k BETWEEN 5 AND 25",
}


@pytest.mark.parametrize("slab_rows", [None, 1024],
                         ids=["one_slab", "five_slabs"])
@pytest.mark.parametrize("shape", SHAPES)
def test_global_aggregate_takes_one_slot(session, shape, slab_rows, tmp_path,
                                         monkeypatch):
    vars_ = {} if slab_rows is None else {"tidb_tpu_max_slab_rows": slab_rows}
    run_checked(session, SHAPES[shape], tmp_path, monkeypatch, **vars_)


# aggregates the device path leaves to the host's HashAgg over a device
# filter: no partial is launched, so there is no slot count to pin — the
# rows still have to be the oracle's, and the counter has to stay still
HOST_AGGREGATED = {
    "bit_ops": "SELECT BIT_AND(a), BIT_OR(a), BIT_XOR(k) FROM h "
               "WHERE a >= 4096",
    "string_min_max": "SELECT MIN(c), MAX(c), COUNT(*) FROM h WHERE a > 7",
}


@pytest.mark.parametrize("shape", HOST_AGGREGATED)
def test_host_aggregated_global_launches_no_partial(session, shape):
    s, sql = session, HOST_AGGREGATED[shape]
    oracle = s.query(sql).rows
    before = _partials("global")
    s.vars.update(tidb_tpu_engine="on", tidb_tpu_row_threshold=1,
                  tidb_tpu_strict="on")
    try:
        assert s.query(sql).rows == oracle and s.last_engine == "tpu"
    finally:
        s.vars.pop("tidb_tpu_strict")
        s.vars["tidb_tpu_engine"] = "off"
    assert _partials("global") == before


def test_empty_selection_still_yields_the_one_row(session, tmp_path,
                                                  monkeypatch):
    rows, _ = run_checked(session, SHAPES["empty_selection"], tmp_path,
                          monkeypatch)
    assert rows == [(0, None, None, None)]


PATHS = {
    # Q3/Q5's shape without the GROUP BY: fused per-slab pipeline
    "join_tree_root": (
        "SELECT COUNT(*), SUM(n), MAX(v) FROM g JOIN dim ON g.k = dim.k "
        "WHERE a > 10", {"tidb_tpu_max_slab_rows": 1024}),
    # the same tree as ONE mega-slab program
    "join_tree_mega_slab": (
        "SELECT COUNT(*), SUM(n), MAX(v) FROM g JOIN dim ON g.k = dim.k "
        "WHERE a > 10", {"tidb_tpu_fused_pipeline": "off"}),
    # ORDER BY … LIMIT over the aggregate: the fused finalize
    "order_by_limit": (
        "SELECT COUNT(*) AS cnt, SUM(n) AS s FROM g WHERE k > 3 "
        "ORDER BY cnt DESC LIMIT 1", {"tidb_tpu_max_slab_rows": 1024}),
    "order_by_limit_join": (
        "SELECT COUNT(*) AS cnt, SUM(v) AS s FROM g JOIN dim ON g.k = dim.k "
        "ORDER BY s LIMIT 1", {"tidb_tpu_max_slab_rows": 1024}),
    # slabs owned by the mesh's devices, partials pinned back
    "pod_partitioned": (
        "SELECT COUNT(*), SUM(n), MIN(b) FROM g WHERE a >= 1024",
        {"tidb_tpu_max_slab_rows": 1024,
         "tidb_tpu_partition_min_rows": 1000}),
    # tidb_tpu_dist_devices: staged per-rank partials, host merge
    "dist_devices_chain": (
        "SELECT COUNT(*), SUM(n), AVG(b) FROM g WHERE a > 10",
        {"tidb_tpu_dist_devices": 4}),
    # …and with an exchange under the aggregate
    "dist_devices_join": (
        "SELECT COUNT(*), SUM(n), MAX(v) FROM g JOIN dim ON g.k = dim.k",
        {"tidb_tpu_dist_devices": 4}),
    # …and as the monolithic shard_map program the staged paths fall back to
    "dist_devices_shard_map": (
        "SELECT COUNT(*), SUM(n), MAX(v) FROM g JOIN dim ON g.k = dim.k",
        {"tidb_tpu_dist_devices": 4, "tidb_tpu_dist_staged": "off",
         "tidb_tpu_dist_staged_exchange": "off"}),
}


@pytest.mark.parametrize("path", PATHS)
def test_global_aggregate_on_every_driver(session, path, tmp_path,
                                          monkeypatch):
    sql, vars_ = PATHS[path]
    if "tidb_tpu_partition_min_rows" in vars_:
        dc.clear()      # the pod entry is built at first touch
    run_checked(session, sql, tmp_path, monkeypatch, **vars_)
    if "tidb_tpu_partition_min_rows" in vars_:
        dc.clear()


def test_global_aggregate_over_delta_slab_and_tombstones(tmp_path,
                                                         monkeypatch):
    """After an INSERT (delta slab) and a DELETE (tombstones) the warm
    entry is amended, not rebuilt: the one-slot partials of base and delta
    merge to what the CPU oracle reads."""
    eng = Engine()
    eng.global_vars["tidb_enable_auto_analyze"] = False
    try:
        s = eng.new_session()
        _load(s, "d", n=3000, seed=5)
        sql = "SELECT COUNT(*), SUM(n), MAX(a), AVG(b) FROM d WHERE k < 35"
        vars_ = {"tidb_tpu_max_slab_rows": 1024}
        first, _ = run_checked(s, sql, tmp_path, monkeypatch, **vars_)
        s.execute("INSERT INTO d VALUES (90001, 0.5, 123.45, 7.0001, 'c1', "
                  "1, NULL), (90002, NULL, -0.01, 8.0002, 'c2', 2, NULL)")
        second, _ = run_checked(s, sql, tmp_path, monkeypatch, **vars_)
        assert second[0][0] == first[0][0] + 2 and second[0][2] == 90002
        s.execute("DELETE FROM d WHERE a % 50 = 7 OR a = 90002")
        third, _ = run_checked(s, sql, tmp_path, monkeypatch, **vars_)
        assert third[0][0] < second[0][0] and third[0][2] == 90001
    finally:
        eng.close()


def test_partials_counter_counts_slabs_for_global_and_none_for_grouped(
        session, tmp_path, monkeypatch):
    """`tidb_tpu_agg_partials_total{grouping="global"}` rises by the
    programs launched with a partial in them — a slab each at a Q6-shaped
    statement's first execution, ONE statement program from its second on
    — and by 0 for a grouped one (which counts under its own grouping)."""
    vars_ = {"tidb_tpu_max_slab_rows": 1024}
    agg_slabs._SPEC_CACHE.clear()
    _, first = run_checked(session, SHAPES["q6_shaped"], tmp_path,
                           monkeypatch, **vars_)
    _, warm = run_checked(session, SHAPES["q6_shaped"], tmp_path,
                          monkeypatch, **vars_)
    # a >= 500 AND a < 4000 over slabs of 1024 rows in insertion order:
    # the zone maps keep slabs 0..3 of 5
    assert (first, warm) == (4, 1)
    s = session
    s.vars.update(tidb_tpu_engine="on", tidb_tpu_row_threshold=1, **vars_)
    try:
        before = {g: _partials(g) for g in ("global", "bounds", "factorize")}
        timeline.start_global(str(tmp_path))
        try:
            assert len(s.query("SELECT k, COUNT(*), SUM(n) FROM g "
                               "GROUP BY k").rows) == 40
            assert len(s.query("SELECT b, COUNT(*) FROM g WHERE a < 100 "
                               "GROUP BY b").rows) == 99
        finally:
            timeline.stop_global()
    finally:
        s.vars.pop("tidb_tpu_max_slab_rows", None)
        s.vars["tidb_tpu_engine"] = "off"
    assert _partials("global") == before["global"]
    assert _partials("bounds") - before["bounds"] == 5
    assert _partials("factorize") - before["factorize"] == 1
    tags = {(e["args"]["grouping"], e["args"]["gcap"])
            for e in timeline.last_events()
            if e["ph"] == "X" and e["name"] == "device.fragment"}
    assert ("bounds", 41) in tags and len(tags) == 2, tags
    assert {g for g, cap in tags} == {"bounds", "factorize"}
    assert all(cap >= 1024 for g, cap in tags if g == "factorize")


def test_one_segment_reduces_flat_and_drops_out_of_range_ids():
    """`ops/segment.py` with one segment: the same answers as the slot
    form, dead rows (id == num_segments) dropped, no (n, 1) intermediate
    in the traced program."""
    from tidb_tpu.ops import segment as seg
    from tidb_tpu.ops.jax_env import jax, jnp
    data = jnp.asarray(np.arange(1, 2001, dtype=np.int64) * 7 - 5000)
    ids = jnp.asarray((np.arange(2000) % 3 == 0).astype(np.int32))
    live = np.asarray(data)[np.asarray(ids) == 0]
    assert int(seg.segment_sum(jnp, data, ids, 1)[0]) == int(live.sum())
    assert int(seg.segment_min(jnp, data, ids, 1)[0]) == int(live.min())
    assert int(seg.segment_max(jnp, data, ids, 1)[0]) == int(live.max())
    none = jnp.ones(2000, dtype=jnp.int32)
    assert seg.segment_sum(jnp, data, none, 1).tolist() == [0]
    assert seg.segment_count(jnp, ids == 0, ids, 1).tolist() == [len(live)]
    text = str(jax.make_jaxpr(
        lambda d, g: seg.segment_sum(jnp, d, g, 1))(data, ids))
    assert "[2000,1]" not in text and "while" not in text, text
