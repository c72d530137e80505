"""Segment (grouped) reduction primitives, numpy + jax backends.

The TPU-first reformulation of TiDB's hash aggregation (SURVEY §7 stage 4):
open-address hash tables have no efficient TPU form, so grouped reduction is
expressed as segment ops — scatter-combine rows into dense group slots. On
numpy these use `ufunc.at` (exact int64 — np.bincount would round through
float64); under jit they lower to `jax.ops.segment_*`, which XLA turns into
efficient sorted-scatter updates.

All functions take `num_segments` statically so jitted shapes stay static.
Rows may carry gid == num_segments-1 padding; callers mask validity instead.
"""

from __future__ import annotations

import numpy as np

# Below this cap, grouped reductions use a masked broadcast-reduce instead of
# a scatter: TPU scatter serializes updates (~70ms for 1M int64 rows on v4),
# while `reduce(where(gid == iota_c, v, id))` stays a fused vector reduction
# (~8ms at cap 16, ~14ms at cap 1024 for 1M rows on v4). The cost grows with
# rows × cap: a v5e reads ≈ 18 ms per int64 state over an 8M-row slab at cap
# 1024, blocked (PERF.md §6, PR 27), which is why an aggregate with no GROUP
# BY asks for ONE segment (fragment._initial_group_cap) and reduces flat, with
# no slot axis at all (≈ 1 ms a state). Exact for
# int64 — no float round trip. The broadcast materializes n×cap values, so
# beyond a materialization budget the reduction runs BLOCKED: lax.map over
# row blocks, each block broadcast-reduced into (cap,) partials, partials
# combined — data streams from HBM once, materialization stays ≤ the budget,
# and no scatter appears (at 64M rows × cap 7 this is ~100× faster than the
# scatter lowering; the SF=10 Q3 regression was exactly this fallback).
MASKED_REDUCE_CAP = 1024
MASKED_REDUCE_WORK = 1 << 27


def _masked_ok(data, num_segments: int) -> bool:
    return num_segments == 1 or (
        num_segments <= MASKED_REDUCE_CAP and
        int(data.shape[0]) * num_segments <= MASKED_REDUCE_WORK)


def _is_np(xp) -> bool:
    return xp is np


def _masked_reduce(xp, data, segment_ids, num_segments, identity, reducer):
    if num_segments == 1:
        # one segment: a plain reduction over the rows whose id is 0 — no
        # (n, 1) slot axis for the compiler to lay out. Out-of-range ids
        # (dead rows) still drop.
        ident = xp.asarray(identity, dtype=data.dtype)
        return reducer(xp.where(segment_ids == 0, data, ident))[None]
    iota = xp.arange(num_segments, dtype=segment_ids.dtype)
    m = segment_ids[:, None] == iota[None, :]
    ident = xp.asarray(identity, dtype=data.dtype)
    return reducer(xp.where(m, data[:, None], ident), axis=0)


def _blocked_masked_reduce(xp, data, segment_ids, num_segments, identity,
                           reducer):
    """Masked reduce in row blocks of ≤ MASKED_REDUCE_WORK materialized
    cells: lax.map(body, blocks) → (B, cap) partials → combine. Out-of-range
    segment ids (dead-row padding) match no slot and drop, exactly like the
    scatter's mode='drop'."""
    from tidb_tpu.ops.jax_env import lax
    n = int(data.shape[0])
    blk = max(MASKED_REDUCE_WORK // num_segments, 1)
    nb = (n + blk - 1) // blk
    pad = nb * blk - n
    ident = xp.asarray(identity, dtype=data.dtype)
    if pad:
        data = xp.concatenate([data, xp.full(pad, ident, dtype=data.dtype)])
        segment_ids = xp.concatenate(
            [segment_ids,
             xp.full(pad, num_segments, dtype=segment_ids.dtype)])
    data2 = data.reshape(nb, blk)
    gid2 = segment_ids.reshape(nb, blk)
    iota = xp.arange(num_segments, dtype=segment_ids.dtype)

    def body(args):
        d, g = args
        m = g[:, None] == iota[None, :]
        return reducer(xp.where(m, d[:, None], ident), axis=0)

    parts = lax.map(body, (data2, gid2))          # (nb, cap)
    return reducer(parts, axis=0)


CUMSUM_BLOCK = 1 << 20


def _cumsum(x):
    """jnp.cumsum over a long vector, in blocks: the TPU compiler takes
    70 s over one int64 cumsum of 48M elements (6 s at 8M, with `while`
    loops), 1.2 s over the same sum as block-wise cumsums plus the blocks'
    offsets — and no loop (PERF.md §6, PR 28)."""
    from tidb_tpu.ops.jax_env import jnp
    n = x.shape[0]
    if n <= CUMSUM_BLOCK or n % CUMSUM_BLOCK:
        return jnp.cumsum(x)
    c = jnp.cumsum(x.reshape(-1, CUMSUM_BLOCK), axis=1)
    totals = c[:, -1]
    return (c + (jnp.cumsum(totals) - totals)[:, None]).reshape(-1)


class SortedRuns:
    """Segment ids of rows that are ALREADY SORTED by group, so that a
    group is a run of adjacent rows: the scatter-free form of the segment
    sums, for group counts beyond the masked reduce.

    A TPU scatter-add serializes: an int64 `jax.ops.segment_sum` of an
    8M-row slab into 8M slots reads 1.1 s a state on a v5e (PERF.md §6,
    PR 28), where a sort of the slab costs 25–30 ms and an int64 cumsum
    10 ms. So a grouped aggregate over many groups sorts its rows by key
    once (ops/factorize.sort_rows) and every sum is a cumsum, ONE gather
    of `cap` elements at the runs' last rows, and an adjacent difference —
    exact in wrapping int64 arithmetic. COUNT and AVG are sums; MIN and MAX
    keep the scatter lowering, so an aggregate that has one does not take
    this path.

    `ends` are the positions of the runs' last rows, ascending (garbage
    beyond `n_runs`); slot g of a result is run g in sorted order. With
    more runs than `cap` the results are invalid and `n_runs` says so (the
    caller's capacity ladder retries)."""

    def __init__(self, ends, n_runs, cap: int):
        from tidb_tpu.ops.jax_env import jnp
        n = ends.shape[0]
        if cap > n:
            ends = jnp.concatenate(
                [ends, jnp.full(cap - n, n - 1, dtype=ends.dtype)])
        self.cap = cap
        self.ends = ends[:cap]
        self.n_runs = n_runs
        self.slot_live = jnp.arange(cap, dtype=jnp.int32) < n_runs

    def at_ends(self, x, fill=0):
        """x at each run's last row → (cap,), `fill` in the dead slots."""
        from tidb_tpu.ops.jax_env import jnp
        return jnp.where(self.slot_live, jnp.take(x, self.ends),
                         jnp.asarray(fill, dtype=x.dtype))

    def sum(self, data):
        from tidb_tpu.ops.jax_env import jnp
        # booleans and int32 scan in int32 (there are < 2³¹ rows)
        acc = jnp.int32 if data.dtype in (jnp.bool_, jnp.int32) \
            else data.dtype
        ends = self.at_ends(_cumsum(data.astype(acc)))
        prev = jnp.concatenate([jnp.zeros(1, dtype=ends.dtype), ends[:-1]])
        return jnp.where(self.slot_live, ends - prev, 0) \
            .astype(jnp.int64 if acc == jnp.int32 else data.dtype)


def segment_sum(xp, data, segment_ids, num_segments: int):
    if isinstance(segment_ids, SortedRuns):
        return segment_ids.sum(data)
    if _is_np(xp):
        out = np.zeros(num_segments, dtype=data.dtype)
        np.add.at(out, segment_ids, data)
        return out
    if _masked_ok(data, num_segments):
        return _masked_reduce(xp, data, segment_ids, num_segments,
                              data.dtype.type(0), xp.sum)
    if num_segments <= MASKED_REDUCE_CAP:
        return _blocked_masked_reduce(xp, data, segment_ids, num_segments,
                                      data.dtype.type(0), xp.sum)
    from tidb_tpu.ops.jax_env import jax
    return jax.ops.segment_sum(data, segment_ids, num_segments=num_segments)


def segment_sum_accurate(xp, data, segment_ids, num_segments: int):
    """Float segment sum with f64-quality accuracy on an f32-only device.

    → (hi, lo) per segment with hi + lo ≈ the exact sum (~48 significant
    bits). TPU has no native f64, and a plain f32 scatter-add absorbs low
    bits once the running sum outgrows individual addends (rel. error up
    to O(n·ε) ≈ 1e-2 at 60M rows). Instead: scale every value by a traced
    power of two (exponent shift — exact in f32), round to int64, and
    accumulate with EXACT integer segment adds; the int result splits back
    into a two-float (hi, lo) pair. Error bound: |err| ≤ n·2⁻ᵏ⁻¹ absolute,
    with 2ᵏ ≈ 2⁶¹/(n·max|x|) — ~1e-12 relative at SF=10 scales.
    Non-finite inputs bypass the int path and propagate (inf/nan) through
    a plain float side-sum. CPU/np accumulates f64 directly (hi, lo=0).
    """
    if _is_np(xp):
        out = np.zeros(num_segments, dtype=np.float64)
        np.add.at(out, segment_ids, data.astype(np.float64))
        return out, np.zeros_like(out)
    if data.dtype == xp.float64:      # CPU jax backend: f64 is native
        s = segment_sum(xp, data, segment_ids, num_segments)
        return s, xp.zeros_like(s)
    finite = xp.isfinite(data)
    x = xp.where(finite, data, xp.zeros_like(data)).astype(xp.float32)
    n_rows = data.shape[0]
    absmax = xp.max(xp.abs(x)) if n_rows else xp.float32(0)
    k = xp.floor(61.0 - xp.log2(xp.maximum(absmax, xp.float32(1e-30)) *
                                (n_rows + 1)))
    k = xp.clip(k, -96.0, 61.0).astype(xp.float32)
    # exp2 is a polynomial approximation on TPU (exp2(30) ≠ 2^30!);
    # ldexp builds the exponent bits exactly, keeping x*scale lossless
    scale = xp.ldexp(xp.float32(1.0), k.astype(xp.int32))
    scaled = xp.round(x * scale).astype(xp.int64)
    ints = segment_sum(xp, scaled, segment_ids, num_segments)
    inv = xp.ldexp(xp.float32(1.0), (-k).astype(xp.int32))
    hi = ints.astype(xp.float32) * inv
    resid = ints - xp.round(hi * scale).astype(xp.int64)
    lo = resid.astype(xp.float32) * inv
    nonfin = segment_sum(xp, xp.where(finite, xp.zeros_like(data), data),
                         segment_ids, num_segments)
    hi = hi + nonfin                  # 0 normally; propagates inf/nan
    return hi, lo


def two_float_add(xp, ahi, alo, bhi, blo):
    """(ahi+alo) + (bhi+blo) as a renormalized two-float pair (Knuth
    two-sum; XLA preserves IEEE ordering so the trick survives jit)."""
    s = ahi + bhi
    bb = s - ahi
    err = (ahi - (s - bb)) + (bhi - bb)
    e = err + alo + blo
    hi = s + e
    lo = e - (hi - s)
    return hi, lo


def segment_count(xp, mask, segment_ids, num_segments: int):
    """Count of True rows per segment → int64."""
    if _is_np(xp):
        out = np.zeros(num_segments, dtype=np.int64)
        np.add.at(out, segment_ids, mask.astype(np.int64))
        return out
    return segment_sum(xp, mask.astype(xp.int64), segment_ids, num_segments)


def segment_min(xp, data, segment_ids, num_segments: int):
    if _is_np(xp):
        out = np.full(num_segments, _max_identity(data.dtype),
                      dtype=data.dtype)
        np.minimum.at(out, segment_ids, data)
        return out
    if _masked_ok(data, num_segments):
        return _masked_reduce(xp, data, segment_ids, num_segments,
                              _max_identity(data.dtype), xp.min)
    if num_segments <= MASKED_REDUCE_CAP:
        return _blocked_masked_reduce(xp, data, segment_ids, num_segments,
                                      _max_identity(data.dtype), xp.min)
    from tidb_tpu.ops.jax_env import jax
    return jax.ops.segment_min(data, segment_ids, num_segments=num_segments)


def segment_max(xp, data, segment_ids, num_segments: int):
    if _is_np(xp):
        out = np.full(num_segments, _min_identity(data.dtype),
                      dtype=data.dtype)
        np.maximum.at(out, segment_ids, data)
        return out
    if _masked_ok(data, num_segments):
        return _masked_reduce(xp, data, segment_ids, num_segments,
                              _min_identity(data.dtype), xp.max)
    if num_segments <= MASKED_REDUCE_CAP:
        return _blocked_masked_reduce(xp, data, segment_ids, num_segments,
                                      _min_identity(data.dtype), xp.max)
    from tidb_tpu.ops.jax_env import jax
    return jax.ops.segment_max(data, segment_ids, num_segments=num_segments)


def segment_any(xp, mask, segment_ids, num_segments: int):
    """True iff any True row lands in the segment."""
    if _is_np(xp):
        out = np.zeros(num_segments, dtype=bool)
        np.logical_or.at(out, segment_ids, mask)
        return out
    return segment_max(xp, mask.astype(xp.int32), segment_ids,
                       num_segments) > 0


def segment_first(xp, data, mask, segment_ids, num_segments: int):
    """First masked value per segment, in row order → (values, found)."""
    n = data.shape[0]
    if _is_np(xp):
        idx = np.full(num_segments, n, dtype=np.int64)
        rows = np.where(mask, np.arange(n, dtype=np.int64), n)
        np.minimum.at(idx, segment_ids, rows)
        found = idx < n
        safe = np.where(found, idx, 0)
        return data[safe], found
    rows = xp.where(mask, xp.arange(n, dtype=xp.int64), n)
    idx = segment_min(xp, rows, segment_ids, num_segments)
    found = idx < n
    safe = xp.where(found, idx, 0)
    return data[safe], found


def _max_identity(dtype):
    dtype = np.dtype(dtype)
    if dtype.kind in "iu":
        return np.iinfo(dtype).max
    if dtype.kind == "f":
        return np.inf
    if dtype.kind == "b":
        return True
    raise AssertionError(f"no identity for {dtype}")


def _min_identity(dtype):
    dtype = np.dtype(dtype)
    if dtype.kind in "iu":
        return np.iinfo(dtype).min
    if dtype.kind == "f":
        return -np.inf
    if dtype.kind == "b":
        return False
    raise AssertionError(f"no identity for {dtype}")
