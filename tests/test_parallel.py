"""Distributed (multi-chip) layer tests on the 8-device virtual CPU mesh —
the in-process cluster pattern of the reference's unistore MPP tests
(SURVEY §4 tier 2: executor/tiflash_test.go runs real MPP plans against an
in-process fake cluster)."""

import numpy as np
import pytest

from tidb_tpu.ops.jax_env import jnp
from tidb_tpu.parallel import make_mesh, shard_rows
from tidb_tpu.parallel import collective as C
from tidb_tpu.parallel.dist_query import (build_agg_join_step,
                                          reference_agg_join)


@pytest.fixture(scope="module")
def mesh(eight_devices):
    return make_mesh(8)


def test_exchange_round_trip(mesh):
    """Hash exchange delivers every live row exactly once, to its owner."""
    from tidb_tpu.ops.jax_env import shard_map
    import jax

    N = 512
    rng = np.random.default_rng(1)
    vals = rng.integers(0, 10 ** 6, N).astype(np.int64)
    live = rng.random(N) < 0.8
    P = jax.sharding.PartitionSpec

    def step(v, lv):
        dest = C.shard_of(v, 8)
        (rv,), r_live, need = C.exchange([v], dest, lv, 8, N)
        return rv, r_live, need

    fn = jax.jit(shard_map(step, mesh=mesh, in_specs=(P("shard"),) * 2,
                           out_specs=(P("shard"), P("shard"), P()),
                           check_vma=False))
    sv, sl = shard_rows(mesh, [vals, live])
    rv, rl, need = fn(sv, sl)
    assert int(need) <= N           # capacity sufficed: nothing dropped
    rv, rl = np.asarray(rv), np.asarray(rl)
    received = sorted(rv[rl].tolist())
    assert received == sorted(vals[live].tolist())
    # ownership: every received row landed on the shard its hash names
    per_shard = rv.reshape(8, -1), rl.reshape(8, -1)
    for shard in range(8):
        v, m = per_shard[0][shard], per_shard[1][shard]
        owners = np.asarray(C.shard_of(jnp.asarray(v[m]), 8))
        assert (owners == shard).all()


def test_exchange_overflow_detected(mesh):
    from tidb_tpu.ops.jax_env import shard_map
    import jax

    N = 256
    vals = np.full(N, 12345, dtype=np.int64)  # all rows → one bucket
    live = np.ones(N, dtype=bool)
    P = jax.sharding.PartitionSpec

    def step(v, lv):
        dest = C.shard_of(v, 8)
        (_rv,), _rl, need = C.exchange([v], dest, lv, 8, 4)
        return need

    fn = jax.jit(shard_map(step, mesh=mesh, in_specs=(P("shard"),) * 2,
                           out_specs=P(), check_vma=False))
    # all 256 rows hash to one destination: the reported need is exact,
    # so the caller can size the retry in ONE recompile
    assert int(fn(*shard_rows(mesh, [vals, live]))) == 32  # 256/8 per shard


def test_distributed_agg_join_matches_oracle(mesh):
    rng = np.random.default_rng(0)
    N, B = 1024, 256
    pk = rng.integers(0, B, N).astype(np.int64)
    px = rng.uniform(0, 10, N)
    pq = rng.uniform(0, 1, N)
    bk = np.arange(B, dtype=np.int64)
    bg = rng.integers(0, 5, B).astype(np.int64)
    bw = rng.uniform(0.5, 1.5, B)
    step = build_agg_join_step(mesh, bucket_cap=N, group_cap=64,
                               filter_limit=0.7)
    args = shard_rows(mesh, [pk, px, pq, np.ones(N, bool),
                             bk, bg, bw, np.ones(B, bool)])
    kv, km, sums, counts, live, need, gneed = step(*args)
    assert int(need) <= N and int(gneed) <= 64  # capacities held
    kv, km, sums, counts, live = map(np.asarray,
                                     (kv, km, sums, counts, live))
    got = {}
    for g, m, s, c, lv in zip(kv, km, sums, counts, live):
        if lv and m:
            assert int(g) not in got  # shards own disjoint group sets
            got[int(g)] = (float(s), int(c))
    ref_s, ref_c = reference_agg_join(pk, px, pq, bk, bg, bw, 0.7)
    assert set(got) == set(ref_s)
    for g in ref_s:
        assert got[g][1] == ref_c[g]
        assert abs(got[g][0] - ref_s[g]) <= 1e-6 * max(1, abs(ref_s[g]))


def test_broadcast_build(mesh):
    from tidb_tpu.ops.jax_env import shard_map
    import jax

    N = 64
    vals = np.arange(N, dtype=np.int64)
    live = np.ones(N, dtype=bool)
    P = jax.sharding.PartitionSpec

    def step(v, lv):
        (g,), gl = C.broadcast_build([v], lv)
        return g.sum(), gl.sum()

    fn = jax.jit(shard_map(step, mesh=mesh, in_specs=(P("shard"),) * 2,
                           out_specs=(P(), P()), check_vma=False))
    s, c = fn(*shard_rows(mesh, [vals, live]))
    assert int(s) == vals.sum() and int(c) == N


def test_cpu_concurrency_process_pool_matches_sequential():
    """tidb_tpu_cpu_concurrency > 1 routes batch partials through the
    spawned process pool (executor/aggregate.go's partial-worker graph
    with OS processes in the worker role — numpy holds the GIL, threads
    cannot scale it). Results must match the sequential path exactly,
    including ci collations and DISTINCT aggs."""
    import numpy as np

    from tidb_tpu.session import Engine
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE mp (g VARCHAR(8) COLLATE utf8mb4_general_ci, "
              "v BIGINT, w DECIMAL(12,2))")
    rng = np.random.default_rng(13)
    names = ["Red", "RED", "blue", "BLUE", "green"]
    s.execute("INSERT INTO mp VALUES " + ",".join(
        f"('{names[int(rng.integers(0, 5))]}',{int(rng.integers(0, 50))},"
        f"{int(rng.integers(0, 10000)) / 100})" for _ in range(200_000)))
    sqls = [
        "SELECT g, COUNT(*), SUM(v), AVG(w), MIN(v), MAX(w) FROM mp "
        "GROUP BY g",
        "SELECT COUNT(*), SUM(v * 2), COUNT(DISTINCT v) FROM mp",
        "SELECT g, COUNT(DISTINCT v) FROM mp GROUP BY g",
    ]
    want = [sorted(map(str, s.query(q).rows)) for q in sqls]
    s.vars["tidb_tpu_cpu_concurrency"] = 4
    try:
        got = [sorted(map(str, s.query(q).rows)) for q in sqls]
    finally:
        s.vars["tidb_tpu_cpu_concurrency"] = 1
    assert got == want


def test_cpu_concurrency_wide_decimal_matches_sequential():
    # review r5: wide-decimal object columns must survive the worker pipe
    # with their Python-int values intact (stringifying corrupts SUM/MIN)
    import numpy as np

    from tidb_tpu.session import Engine
    s = Engine().new_session()
    s.execute("CREATE TABLE wd (g BIGINT, w DECIMAL(30,2))")
    s.execute("INSERT INTO wd VALUES " + ",".join(
        f"({i % 3},{10**20 + i}.25)" for i in range(5000)))
    q = "SELECT g, SUM(w), MIN(w), MAX(w) FROM wd GROUP BY g ORDER BY g"
    want = s.query(q).rows
    s.vars["tidb_tpu_cpu_concurrency"] = 2
    try:
        got = s.query(q).rows
    finally:
        s.vars["tidb_tpu_cpu_concurrency"] = 1
    assert got == want
