"""Distributed query steps: whole MPP task DAGs as one shard_map program.

The reference plans an MPP query as fragments connected by exchanges
(planner/core/fragment.go:64, executed by unistore's mppExec trees,
cophandler/mpp.go:332-347). Here the WHOLE fragment graph — scan-filter,
hash exchange, join, two-phase aggregate — traces into a single jitted
shard_map program: XLA schedules the collectives on ICI, overlapping them
with per-shard compute, instead of a goroutine pumping gRPC tunnels.

`build_agg_join_step` is the flagship distributed step (the Q3 shape:
filter → hash-exchange join → grouped aggregate) used by the multi-chip
dry-run and the distributed benchmarks.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import numpy as np

from tidb_tpu.ops.jax_env import jax, jnp, lax
from tidb_tpu.parallel import collective as C

AXIS = "shard"


def _local_grouped_sum(keys, live, values_list, cap: int):
    """Per-shard partial aggregation: factorize + segment ops (the partial
    half of the reference's 2-phase HashAgg, aggregate.go:127-164)."""
    from tidb_tpu.ops import factorize as F
    gids, n_groups, rep = F.factorize(keys, live, cap)
    gids = jnp.where(live, gids, jnp.int32(cap))
    sums = [jax.ops.segment_sum(jnp.where(live, v, jnp.zeros_like(v)),
                                gids, num_segments=cap)
            for v in values_list]
    counts = jax.ops.segment_sum(jnp.where(live, jnp.int64(1),
                                           jnp.int64(0)), gids,
                                 num_segments=cap)
    slot_live = jnp.arange(cap, dtype=jnp.int32) < n_groups
    key_out = [(jnp.asarray(v)[rep], jnp.asarray(m)[rep] & slot_live)
               for v, m in keys]
    # n_groups is the TRUE distinct count (factorize counts before
    # clamping) — the caller's ladder resizes to exact need in ONE step
    return key_out, sums, counts, slot_live, n_groups


def _owned_final_merge(gkeys, gsums, gcounts, gslot_live, cap: int,
                       n_shards: int):
    """Final phase: each shard merges the groups it owns (hash of the key
    VALUE, comparable across shards — local factorize ids are not)."""
    from tidb_tpu.ops import factorize as F
    rank = lax.axis_index(AXIS)
    owner = C.shard_of(C.mix_key_code(gkeys), n_shards)
    own = gslot_live & (owner == rank)
    gids, n_own, rep = F.factorize(gkeys, own, cap)
    gids = jnp.where(own, gids, jnp.int32(cap))
    f_sums = [jax.ops.segment_sum(jnp.where(own, s, jnp.zeros_like(s)),
                                  gids, num_segments=cap) for s in gsums]
    f_counts = jax.ops.segment_sum(jnp.where(own, gcounts,
                                             jnp.zeros_like(gcounts)),
                                   gids, num_segments=cap)
    out_live = jnp.arange(cap, dtype=jnp.int32) < n_own
    f_keys = [(jnp.asarray(v)[rep], jnp.asarray(m)[rep] & out_live)
              for v, m in gkeys]
    return f_keys, f_sums, f_counts, out_live, n_own


def build_agg_join_step(mesh, bucket_cap: int, group_cap: int,
                        filter_limit: float):
    """Jitted distributed step for the Q3 shape:

        SELECT b.g, SUM(p.x * b.w), COUNT(*)
        FROM probe p JOIN build b ON p.k = b.k
        WHERE p.q < filter_limit GROUP BY b.g

    Inputs (all row-sharded over the mesh axis):
      probe:  pk (N,) i64, px pq (N,) float, p_live (N,) bool
      build:  bk (N,) i64, bg (N,) i64, bw (N,) float, b_live (N,) bool
    Output (per shard, concatenated by shard_map): group keys, sums,
    counts, live slots — each shard owns a disjoint subset of groups —
    plus two replicated overflow flags: `need` (largest per-destination
    exchange row count; need > bucket_cap means rows were DROPPED and
    the result is truncated) and `group_need` (largest per-shard true
    group count; group_need > group_cap means groups were conflated).
    Callers must check both — run_agg_join below is the ladder driver
    that re-executes with exact-need capacities instead.

    Parallelism content: local filter (region-parallel scan), all_to_all
    hash exchange of BOTH sides (ExchangeType_Hash), per-shard sort-probe
    join (no hash table), two-phase aggregate with value-owned final merge.
    """
    from tidb_tpu.ops.jax_env import shard_map
    from tidb_tpu.ops import join as J

    n_shards = mesh.devices.size
    P = jax.sharding.PartitionSpec

    def step(pk, px, pq, p_live, bk, bg, bw, b_live):
        # 1. local scan filter (pushed-down selection)
        p_live2 = p_live & (pq < filter_limit)
        # 2. hash-exchange both sides so equal keys co-locate
        pdest = C.shard_of(pk, n_shards)
        (rpk, rpx), rp_live, p_over = C.exchange(
            [pk, px], pdest, p_live2, n_shards, bucket_cap)
        bdest = C.shard_of(bk, n_shards)
        (rbk, rbg, rbw), rb_live, b_over = C.exchange(
            [bk, bg, bw], bdest, b_live, n_shards, bucket_cap)
        # 3. per-shard unique-build join via sort + binary search
        nb = rbk.shape[0]
        npr = rpk.shape[0]
        both = jnp.concatenate([rbk, rpk])
        both_live = jnp.concatenate([rb_live, rp_live])
        codes, cvalid = J.combine_keys(
            [(both, jnp.ones_like(both_live))], both_live)
        midx, matched, _uni = J.build_probe(
            codes[:nb], cvalid[:nb], rb_live,
            codes[nb:], cvalid[nb:], rp_live)
        jg = jnp.take(rbg, midx)          # build-side group key per probe row
        jw = jnp.take(rbw, midx)          # build-side payload
        j_live = matched
        # 4. two-phase aggregate: partial by local groups…
        keys = [(jg, jnp.ones(npr, dtype=bool))]
        pkeys, psums, pcounts, pslot, p_ng = _local_grouped_sum(
            keys, j_live, [rpx * jw], group_cap)
        # …gather partials, merge owned groups
        gkeys, gstates, gslot = C.gather_partials(
            pkeys, [tuple(psums) + (pcounts,)], pslot)
        gsums = [gstates[0][0]]
        gcounts = gstates[0][1]
        fkeys, fsums, fcounts, fl, n_own = _owned_final_merge(
            gkeys, gsums, gcounts, gslot, group_cap, n_shards)
        need = jnp.maximum(p_over, b_over).astype(jnp.int32)
        group_need = lax.pmax(
            jnp.maximum(p_ng, n_own).astype(jnp.int32), AXIS)
        return (fkeys[0][0], fkeys[0][1], fsums[0], fcounts, fl,
                need, group_need)

    sharded = shard_map(
        step, mesh=mesh,
        in_specs=(P(AXIS),) * 8,
        out_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS), P(AXIS), P(), P()),
        check_vma=False)
    return jax.jit(sharded)


def run_agg_join(mesh, pk, px, pq, bk, bg, bw, *, bucket_cap: int,
                 group_cap: int, filter_limit: float, p_live=None,
                 b_live=None, guard=None, max_bucket_cap: int = 1 << 22,
                 max_group_cap: int = 1 << 20):
    """Host-side escalation-ladder driver for build_agg_join_step.

    Runs the distributed step and consumes its overflow flags: an
    exchange `need` or `group_need` past the current capacity triggers an
    exact-need resize + ONE recompile per overflowed structure, each
    attempt charged against the ladder's backoff budget and
    guard-checkpointed between recompiles. When a capacity limit is hit
    the ladder is exhausted and a typed CapacityError raises — truncated
    rows are never returned.

    → ({group_key: (sum, count)}, EscalationStats)."""
    from tidb_tpu.errors import BackoffExhausted, CapacityError
    from tidb_tpu.parallel import shard_rows
    from tidb_tpu.util import failpoint
    from tidb_tpu.util.escalation import CapacityLadder

    n, b = len(pk), len(bk)
    p_live = np.ones(n, dtype=bool) if p_live is None else p_live
    b_live = np.ones(b, dtype=bool) if b_live is None else b_live
    ladder = CapacityLadder(guard=guard)
    while True:
        if guard is not None:
            guard.check("device-dispatch")
        step = build_agg_join_step(mesh, bucket_cap=bucket_cap,
                                   group_cap=group_cap,
                                   filter_limit=filter_limit)
        args = shard_rows(mesh, [pk, px, pq, p_live, bk, bg, bw, b_live])
        kv, km, sums, counts, live, need, gneed = step(*args)
        need, gneed = int(need), int(gneed)
        retry = False
        if need > bucket_cap:
            failpoint.inject("exchange-overflow")
            if bucket_cap >= max_bucket_cap:
                ladder.fallback("exchange")
                raise CapacityError(
                    f"exchange needs {need} rows/bucket but the ladder is "
                    f"exhausted (cap {bucket_cap}, limit {max_bucket_cap})")
            bucket_cap = ladder.resize("exchange", bucket_cap, need=need,
                                       max_cap=max_bucket_cap, lo=8)
            retry = True
        if gneed > group_cap:
            if group_cap >= max_group_cap:
                ladder.fallback("group")
                raise CapacityError(
                    f"aggregate needs {gneed} group slots but the ladder "
                    f"is exhausted (cap {group_cap}, "
                    f"limit {max_group_cap})")
            group_cap = ladder.resize("group", group_cap, need=gneed,
                                      max_cap=max_group_cap, lo=8)
            retry = True
        if not retry:
            break
        try:
            ladder.attempt("agg-join")
        except BackoffExhausted as e:
            ladder.fallback("budget")
            raise CapacityError(
                "distributed agg-join recompile budget exhausted") from e
    out = {}
    kv, km, sums, counts, live = map(np.asarray,
                                     (kv, km, sums, counts, live))
    for g, m, sv, c, lv in zip(kv, km, sums, counts, live):
        if lv and m:
            out[int(g)] = (float(sv), int(c))
    return out, ladder.stats


def reference_agg_join(pk, px, pq, bk, bg, bw, filter_limit):
    """Single-host numpy oracle for build_agg_join_step."""
    keep = pq < filter_limit
    bmap = {int(k): (int(g), float(w)) for k, g, w in zip(bk, bg, bw)}
    sums, counts = {}, {}
    for k, x, ok in zip(pk, px, keep):
        if not ok:
            continue
        hit = bmap.get(int(k))
        if hit is None:
            continue
        g, w = hit
        sums[g] = sums.get(g, 0.0) + float(x) * w
        counts[g] = counts.get(g, 0) + 1
    return sums, counts
