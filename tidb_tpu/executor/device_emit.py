"""Shared traced emission helpers for device programs.

One implementation of the root reductions — grouped aggregation (sort-
factorize or stats-informed perfect-hash) and window evaluation — used by
both the linear-chain fragment programs (executor/agg_slabs.py) and the
join-tree / distributed programs (executor/tree_fragment.py,
dist_fragment.py). The reference splits the same logic between
executor/aggregate.go and unistore's cophandler/mpp_exec.go; here it is
literally one function.

All helpers are pure traced functions of (ctx, live, plan node): `ctx` is
an expression EvalContext over device arrays, `live` the row-liveness mask
(the sel vector analog).

Every stage traces under a `jax.named_scope` of its name (`STAGES`), so each
device operation's metadata (`op_name`: `jit(<program>)/.../<stage>/<op>`)
says which stage emitted it, and a profile can be read by stage
(benchmarks/device_scopes.py). The programs' own filter, projection and
join-probe code (fragment.py, tree_fragment.py) enters `stage()` too. Where
stages nest (finalize holds merge and sort) the innermost names the
operation. Trace-time only: nothing runs per call.
"""

from __future__ import annotations

import functools
import itertools
from typing import List, Sequence

from tidb_tpu.chunk import compress
from tidb_tpu.expression import ColumnRef, EvalContext
from tidb_tpu.expression.aggfuncs import AggFunc
from tidb_tpu.ops import factorize as F, segment as seg, window as W
from tidb_tpu.ops.jax_env import jax, jnp, lax
from tidb_tpu.ops.segment import SortedRuns
from tidb_tpu.planner.physical import (PhysHashAgg, PhysLimit, PhysSort,
                                       PhysTopN, PhysWindow)
from tidb_tpu.types import TypeKind
from tidb_tpu.util import timeline
from tidb_tpu.util.observability import REGISTRY


STAGES = ("decode", "filter", "project", "join_probe", "agg", "merge",
          "finalize", "sort", "window", "partition")


def stage(name: str):
    """`jax.named_scope(name)` for one of `STAGES`."""
    return jax.named_scope(name)


def _staged(name: str):
    """Trace the decorated emit function under `stage(name)`."""
    def deco(fn):
        @functools.wraps(fn)
        def emit(*args, **kwargs):
            with stage(name):
                return fn(*args, **kwargs)
        return emit
    return deco


@_staged("decode")
def emit_decode(layout, slab, cap: int):
    """Traced decode of one compressed column slab INSIDE the fragment:
    (words, mask_words[, dictvals]) → (vals, valid) in the logical
    dtype. A gather-free broadcast shift/mask (plus one take for dict
    layouts) traced into the consuming scan→filter→…→agg program, so
    decode adds zero extra launches and raw bytes never cross PCIe.

    The decoded column sits behind an optimization barrier: it is
    computed once per program and read by every consumer. Without it XLA
    clones the unpack into each consuming fusion — one per aggregate —
    and the TPU compiler then took six minutes over Q1's partial at an
    8M-row slab (13 s with the barrier), for the price of one HBM round
    trip of the decoded column per slab.

    A `delta` column says which scan its decode took
    (`compress.delta_scan`: int32 | wide | plain), once per column and
    traced program since this runs while tracing: the always-on counter
    `tidb_tpu_delta_decode_programs_total{scan=}` and the tag `delta_scan`
    on the span that covers the trace (the program's first `launch`)."""
    decoded = compress.decode_slab(layout, slab, cap, jnp)  # validates
    if layout.kind == "delta":
        scan = compress.delta_scan(layout, cap)
        REGISTRY.inc("tidb_tpu_delta_decode_programs_total", {"scan": scan})
        timeline.tag(delta_scan=scan)
    return lax.optimization_barrier(decoded)


@_staged("sort")
def emit_sort(keys, descs, live):
    """Traced full-sort permutation under ORDER BY semantics → (perm,
    n_live). Thin named wrapper over ops/factorize.sort_perm: keys are
    rank-encoded per column exactly like executor/sort.py's host
    rank_keys, so direction + MySQL NULL ordering (NULLs first ASC,
    last DESC) behave identically on device and host."""
    return F.sort_perm(keys, descs, live)


@_staged("sort")
def emit_topk(keys, descs, live, k: int):
    """Traced top-k row selection → (idx (k,), n_out). Same rank
    encoding as emit_sort; k is static (min(count+offset, cap))."""
    return F.topn(keys, descs, live, k)


@_staged("agg")
def emit_distinct(gids, v, m, live, n: int, keys, pairs_out: bool,
                  pair_cap: int = 0, vcols=None):
    """Traced per-batch DISTINCT dedup for one aggregate argument tuple →
    (first_mask, pairs). `first_mask` marks the first live occurrence of
    each (group, value) pair — the state-update mask. With `pairs_out`,
    `pairs` is (cols, n_pairs): the deduped (group-keys, args...) tuples
    for the cross-slab host merge, truncated to `pair_cap` output slots
    (0 = no truncation). `vcols` is the raw per-arg (value, mask) column
    list shipped in the pair output — for multi-arg DISTINCT, `v` is a
    batch-local combined code that means nothing across slabs, so the
    pairs carry real values instead. The factorize itself ALWAYS runs at
    the full batch capacity so first_mask stays exact; only the pair
    OUTPUT arrays shrink — n_pairs reports the TRUE count, so the driver
    can detect a truncated pair set and resize through the capacity
    ladder."""
    first, _pg, n_pairs, rep = F.distinct_pair_factorize(
        gids, v, m, live, n)
    if not pairs_out:
        return first, None
    pc = min(pair_cap, n) if pair_cap else n
    rep_p = rep[:pc]
    pslot = jnp.arange(pc, dtype=jnp.int32) < n_pairs
    cols = [(jnp.asarray(kv)[rep_p], jnp.asarray(km)[rep_p] & pslot)
            for kv, km in keys]
    for av, _am in (vcols if vcols is not None else [(v, m)]):
        cols.append((jnp.asarray(av)[rep_p], pslot))
    return first, (cols, n_pairs)


def emit_root(ctx: EvalContext, live, root, aggs=None, group_cap: int = 0,
              key_bounds=None, pairs_out: bool = False, slab_cap: int = 0,
              pair_cap: int = 0):
    """Root reduction dispatch for a fused pipeline: the single emit
    point every device program (linear chain, join tree, fused per-slab
    pipeline, distributed shard) routes its root operator through.

    → HashAgg: emit_agg's {keys, states, n_groups, slot_live[, pairs]};
      TopN/Sort: {cols, n_out} (gathered in sorted order, truncated to
      k for TopN); Window: emit_window's {cols, live}; any row root
      (Selection/Projection/Join): padded {cols, live}."""
    if isinstance(root, PhysHashAgg):
        return emit_agg(ctx, live, root, aggs, group_cap, key_bounds,
                        pairs_out=pairs_out, pair_cap=pair_cap)
    if isinstance(root, PhysLimit):
        # LIMIT pushdown (no ORDER BY): the first offset+count live rows
        # in row order — a stable partition of the live mask, the
        # degenerate keyless emit_topk
        with stage("sort"):
            n = live.shape[0]
            k = min(root.count + root.offset, slab_cap or n)
            idx = jnp.argsort(jnp.logical_not(live), stable=True)[:k]
            n_out = jnp.minimum(live.sum().astype(jnp.int32), jnp.int32(k))
            out_cols = [ctx.column(i) for i in range(len(root.schema))]
            gathered = [(jnp.asarray(v)[idx], jnp.asarray(m)[idx])
                        for v, m in out_cols]
        return {"cols": gathered, "n_out": n_out}
    if isinstance(root, (PhysTopN, PhysSort)):
        with stage("sort"):
            keys = [e.eval(ctx) for e in root.by]
            out_cols = [ctx.column(i) for i in range(len(root.schema))]
            if isinstance(root, PhysTopN):
                k = min(root.count + root.offset,
                        slab_cap or live.shape[0])
                idx, n_out = emit_topk(keys, root.descs, live, k)
            else:
                idx, n_out = emit_sort(keys, root.descs, live)
            gathered = [(jnp.asarray(v)[idx], jnp.asarray(m)[idx])
                        for v, m in out_cols]
        return {"cols": gathered, "n_out": n_out}
    if isinstance(root, PhysWindow):
        return emit_window(ctx, live, root)
    out_cols = [ctx.column(i) for i in range(len(root.schema))]
    return {"cols": [(jnp.asarray(v), jnp.asarray(m))
                     for v, m in out_cols], "live": live}


def partials_of(partials):
    """The per-slab partial outputs as the merge programs take them —
    (key_cols, states, slot_live), each leaf a LIST with one array per
    partial, which `stack_partials` concatenates inside the trace. No
    device work here: the stacking used to be one eager concatenate per
    array on the host's clock (27 ms a statement over six slabs)."""
    p0 = partials[0]
    key_cols = [tuple([p["keys"][kc][f] for p in partials]
                      for f in range(2))
                for kc in range(len(p0["keys"]))]
    states = [tuple([p["states"][ai][f] for p in partials]
                    for f in range(len(p0["states"][ai])))
              for ai in range(len(p0["states"]))]
    return key_cols, states, [p["slot_live"] for p in partials]


@_staged("merge")
def stack_partials(key_cols, states, slot_live):
    """`partials_of`'s lists → one stacked partial (traced). Already
    stacked arrays pass through."""
    if not isinstance(slot_live, (list, tuple)):
        return key_cols, states, slot_live
    cat = jnp.concatenate
    return ([(cat(v), cat(m)) for v, m in key_cols],
            [tuple(cat(f) for f in st) for st in states], cat(slot_live))


@_staged("merge")
def emit_merge(root, aggs: List[AggFunc], group_cap: int, key_cols,
               states, slot_live):
    """Root merge of stacked per-slab agg partials: re-factorize the
    concatenated partial keys under their slot_live masks (ragged caps
    are fine — dead slots map past the cap), sanitize dead slots to
    identities, scatter-merge states (AggFunc.merge is the same segment
    op as update — SURVEY A.4). One implementation shared by the chain
    program's merge and the fused pipeline's root-merge program."""
    # where each partial's slots end in the stack (None: handed in stacked)
    ends = list(itertools.accumulate(int(a.shape[0]) for a in slot_live)) \
        if isinstance(slot_live, (list, tuple)) else None
    key_cols, states, slot_live = stack_partials(key_cols, states, slot_live)
    cap = group_cap
    if root.group_exprs:
        gids, n_final, rep = F.factorize(key_cols, slot_live, cap)
        gids = jnp.where(slot_live, gids, jnp.int32(cap))
        key_out = [(jnp.asarray(v)[rep], jnp.asarray(m)[rep] &
                    (jnp.arange(cap) < n_final)) for v, m in key_cols]
    else:
        gids = jnp.where(slot_live, jnp.int32(0), jnp.int32(cap))
        n_final = jnp.int32(1)
        key_out = []
    out_states = []
    for agg, partial in zip(aggs, states):
        clean = tuple(
            jnp.where(slot_live, arr,
                      jnp.zeros_like(arr) if arr.dtype != jnp.bool_
                      else jnp.zeros_like(arr))
            for arr in partial)
        st = agg.init(jnp, cap)
        if agg.float_sums and ends:
            # a floating-point sum folds partial by partial, in slab order:
            # a partial holds a group once, so each reduction adds one
            # value to zeros, and the adds between them are the program's
            # own. ONE reduction over the stack adds in whatever order the
            # compiler gives the program it stands in, and the per-slab
            # merge and a statement program then differ in the last place
            for lo, hi in zip([0] + ends, ends):
                st = agg.merge(jnp, st, gids[lo:hi], cap,
                               tuple(arr[lo:hi] for arr in clean))
        else:
            st = agg.merge(jnp, st, gids, cap, clean)
        out_states.append(st)
    return {"keys": key_out, "states": out_states, "n_groups": n_final}


def _order_keys(order_root, aggs, nk: int, keys, states, live):
    """The order root's sort keys over an aggregate's output slots →
    (keys [(values, valid)], their directions): a group key is its slot
    column, an aggregate its `AggFunc.order_keys` (one or more columns,
    all in the aggregate's direction)."""
    okeys, descs = [], []
    for e, desc in zip(order_root.by, order_root.descs):
        cols = [keys[e.index]] if e.index < nk else \
            aggs[e.index - nk].order_keys(jnp, tuple(states[e.index - nk]))
        for v, m in cols:
            okeys.append((jnp.asarray(v), jnp.asarray(m) & live))
            descs.append(desc)
    return okeys, descs


@_staged("finalize")
def emit_finalize(root, order_root, aggs: List[AggFunc], group_cap: int,
                  key_cols, states, slot_live):
    """Fused finalize: agg merge → finalize expressions → root ORDER BY /
    TopN as ONE trace, so a warm analytic query is `slabs + 1` programs
    total. Order keys referencing group keys read the merged key slots;
    keys referencing aggregate outputs evaluate AggFunc.order_keys
    IN-TRACE (the fragment gate admits count/sum/avg/min/max over narrow
    results and SUM's limb planes — other wide-decimal finals are
    host-only). The sort/TopN runs on
    the rank encoding of emit_sort/emit_topk, so direction + MySQL NULL
    ordering match executor/sort.py exactly.

    → {keys, states, n_groups, n_out}: keys/states gathered in output
    order (truncated to k for TopN); n_groups is the TRUE merged group
    count for the caller's capacity-ladder validation."""
    merged = emit_merge(root, aggs, group_cap, key_cols, states, slot_live)
    cap = group_cap
    live = jnp.arange(cap, dtype=jnp.int32) < merged["n_groups"]
    nk = len(root.group_exprs)
    okeys, descs = _order_keys(order_root, aggs, nk, merged["keys"],
                               merged["states"], live)
    if isinstance(order_root, PhysTopN):
        k = min(order_root.count + order_root.offset, cap)
        idx, n_out = emit_topk(okeys, descs, live, k)
    else:
        idx, n_out = emit_sort(okeys, descs, live)
    keys_o = [(jnp.asarray(v)[idx], jnp.asarray(m)[idx])
              for v, m in merged["keys"]]
    states_o = [tuple(jnp.asarray(a)[idx] for a in st)
                for st in merged["states"]]
    return {"keys": keys_o, "states": states_o,
            "n_groups": merged["n_groups"], "n_out": n_out}


@_staged("agg")
def emit_agg(ctx: EvalContext, live, root, aggs: List[AggFunc],
             group_cap: int, key_bounds=None, pairs_out: bool = False,
             pair_cap: int = 0):
    """Grouped-aggregation partial over one batch → {keys, states,
    n_groups, slot_live}. `key_bounds` (ops/factorize.KeyBounds) names
    the lowering: SLOTS — a direct packed code + segment ops, no sort
    (the perfect-hash path); RUNS — this slab only hands out its rows
    (`group_rows`); None — sort-based factorize.

    With `pairs_out`, the result gains "pairs": {agg_idx: (cols,
    n_pairs)} — the deduped (group-keys, value) tuples of every DISTINCT
    agg, for the cross-slab host merge (host_decode.merge_distinct_states).
    The pair factorize is computed ONCE per distinct agg and shared with
    the state first-occurrence mask: lax.sort compiles are the dominant
    device-program compile cost (ops/factorize.py docstring), so no sort
    runs twice."""
    n = live.shape[0]
    cap = group_cap
    if root.group_exprs and getattr(root, "rollup", False):
        # WITH ROLLUP: tile the batch (nk+1)× — copy l rolls up the LAST
        # l group keys (validity masked off, so the rolled-up key is NULL
        # for free) and a grouping-level column joins the factorize keys
        # LAST, keeping a genuinely-NULL key group separate from the
        # super-aggregate over it.  key_out carries the level column as a
        # trailing internal column: emit_merge / the host merges
        # re-factorize over ALL key columns generically, and the drivers
        # decode only the first nk into the result chunk.
        ctx, live = _rollup_tile(ctx, live, root)
        n = live.shape[0]
        nk = len(root.group_exprs)
        n0 = n // (nk + 1)
        lev = jnp.repeat(jnp.arange(nk + 1, dtype=jnp.int64), n0)
        keys = [e.eval(ctx) for e in root.group_exprs]
        keys = [(jnp.asarray(v), jnp.asarray(m) & (lev < nk - i))
                for i, (v, m) in enumerate(keys)]
        fkeys = keys + [(lev, jnp.ones_like(live))]
        gids, n_groups, rep = F.factorize(fkeys, live, cap)
        gids = jnp.where(live, gids, jnp.int32(cap))
        key_out = [(jnp.asarray(v)[rep], jnp.asarray(m)[rep] &
                    (jnp.arange(cap) < n_groups)) for v, m in fkeys]
        slot_live = jnp.arange(cap, dtype=jnp.int32) < n_groups
    elif root.group_exprs and F.grouping_mode(key_bounds) == F.RUNS:
        return group_rows(ctx, live, root, key_bounds.bounds)
    elif root.group_exprs and F.grouping_mode(key_bounds) == F.SLOTS:
        keys, gids, n_groups, key_out, slot_live = _perfect_groups(
            ctx, live, root, cap, key_bounds.bounds)
    elif root.group_exprs:
        keys = [e.eval(ctx) for e in root.group_exprs]
        gids, n_groups, rep = F.factorize(keys, live, cap)
        # dead rows → out-of-range id: segment ops drop them, which is
        # required for order-sensitive states (first_row)
        gids = jnp.where(live, gids, jnp.int32(cap))
        key_out = [(jnp.asarray(v)[rep], jnp.asarray(m)[rep] &
                    (jnp.arange(cap) < n_groups)) for v, m in keys]
        slot_live = jnp.arange(cap, dtype=jnp.int32) < n_groups
    else:
        keys = []
        gids = jnp.where(live, jnp.int32(0), jnp.int32(cap))
        n_groups = jnp.int32(1)
        key_out = []
        slot_live = jnp.arange(cap, dtype=jnp.int32) < 1
    dvals, dfirst, dpairs = {}, {}, {}
    for ai, desc in enumerate(root.aggs):
        if not (desc.distinct and desc.args):
            continue
        v, m, vcols = _distinct_arg(ctx, live, desc)
        dvals[ai] = (v, m)
        first, pairs = emit_distinct(gids, v, m, live, n, keys,
                                     pairs_out, pair_cap, vcols=vcols)
        dfirst[ai] = first
        if pairs is not None:
            dpairs[ai] = pairs
    states = _agg_states(ctx, live, root, aggs, gids, cap, n,
                         dfirst, dvals,
                         key_bounds.arg_bits if key_bounds is not None else ())
    out = {"keys": key_out, "states": states, "n_groups": n_groups,
           "slot_live": slot_live}
    if pairs_out:
        out["pairs"] = dpairs
    return out


def sorted_runs_ok(root) -> bool:
    """Can this aggregate's partials group by sorted runs
    (`_sorted_runs_agg`)? Every state must be a sum of a per-row value —
    COUNT, SUM, AVG over at most one 1-D argument; DISTINCT wants row
    ids, MIN/MAX a scatter identity, ROLLUP the tiled factorize. And the
    value must be an INTEGER (scaled DECIMALs are): a run's sum is the
    difference of two prefix sums over all rows, exact in wrapping int64
    and nowhere else — a float group near 1 beside one near 1e16 would
    come out 0 or 8."""
    if not root.group_exprs or getattr(root, "rollup", False):
        return False
    if any(e.ftype.kind.is_string or e.ftype.is_wide_decimal
           for e in root.group_exprs):
        return False    # keys unpack into their numeric dtypes
    for d in root.aggs:
        if d.distinct or d.name not in ("count", "sum", "avg") \
                or len(d.args) > 1:
            return False
        if d.args and (d.args[0].ftype.kind.is_string
                       or d.args[0].ftype.kind.is_float or (
                isinstance(d.args[0], ColumnRef)
                and d.args[0].ftype.is_wide_decimal)):
            return False
    return True


def group_rows(ctx: EvalContext, live, root, key_bounds):
    """A slab's part in grouping by SORTED RUNS — for MANY groups whose
    keys have known bounds: no reduction here, only each row's packed key
    word(s) and, per aggregate with an argument, its value and validity.
    The statement's rows are then sorted ONCE, all slabs together
    (ops/factorize.sort_rows, a program of its own that every statement
    shares), and `emit_runs_finalize` reduces the runs of equal keys by
    scans. Against per-slab sort-factorize partials and their merge this
    saves the per-key rank sorts, the scatters back to row order, the
    int64 scatter-adds (1.1 s a state and 8M-row slab on a v5e) and the
    re-sort of the partial slots.
    → {"words", "payloads", "live", "n_groups": 0} (nothing here can
    overflow a group capacity)."""
    keys = [e.eval(ctx) for e in root.group_exprs]
    payloads = []
    for desc in root.aggs:
        if desc.args:
            v, m = desc.args[0].eval(ctx)
            payloads += [jnp.asarray(v), jnp.asarray(m) & live]
    return {"words": F.pack_words(keys, key_bounds), "payloads": payloads,
            "live": live, "n_groups": jnp.int32(0)}


@_staged("agg")
def _runs_states(root, aggs, runs, payloads, live_s, arg_bits=()):
    """One state tuple per aggregate over rows sorted into runs. Every
    state that is a sum of a per-row integer (`AggFunc.row_sums`: all that
    `sorted_runs_ok` admits) comes out of ONE call of `seg.run_sums` for
    the whole aggregate: the fields whose width is known — `arg_bits`
    (ops/factorize.KeyBounds) per aggregate, a validity's one bit — share
    int64 words, and a WORD is scanned and gathered at the run ends, not a
    state; the constant-0 limbs of a narrow value under a wide SUM cost
    nothing. An argument that several aggregates name is read from the
    first one's payload (the sort carried equal copies), so `SUM(x)` and
    `AVG(x)` share their fields and every aggregate over one validity its
    count. Says what the traced program holds, once a trace: tag
    `run_sums` = `<words scanned>/<state arrays>` on the span that covers
    the trace, and counter `tidb_tpu_run_sum_scans_total{range=
    bounded|whole}`, the scans by whether the word's fields had known
    widths."""
    n = live_s.shape[0]
    inputs, named, i = [], {}, 0
    for desc in root.aggs:
        if desc.args:
            arg = desc.args[0]
            key = arg.index if isinstance(arg, ColumnRef) else id(arg)
            if key not in named:
                named[key] = (payloads[i], payloads[i + 1] & live_s)
            inputs.append(named[key])
            i += 2
        else:
            inputs.append((jnp.zeros(n, dtype=jnp.int64), live_s))
    plans = [agg.row_sums(jnp, v, m, bits) for agg, (v, m), bits in zip(
        aggs, inputs, arg_bits or [None] * len(aggs))]
    columns = [c for plan in plans if plan for c in plan if c is not None]
    states = _fill_states(aggs, plans, inputs, runs, runs.cap,
                          seg.run_sums(columns, runs, n))
    bounded, whole = seg.run_sum_scans(columns, n)
    timeline.tag(run_sums=f"{bounded + whole}"
                          f"/{sum(len(st) for st in states)}")
    for known, scans in (("bounded", bounded), ("whole", whole)):
        if scans:
            REGISTRY.inc("tidb_tpu_run_sum_scans_total", {"range": known},
                         scans)
    return states


def _fill_states(aggs, plans, inputs, gids, cap: int, sums):
    """The state tuples from the sums of the aggregates' planned columns
    (`AggFunc.row_sums`, in the columns' order); an aggregate without a
    plan runs its own `update`."""
    sums = iter(sums)
    states = []
    for agg, plan, (v, m) in zip(aggs, plans, inputs):
        st = agg.init(jnp, cap)
        if plan:
            states.append(tuple(a if c is None else a + next(sums)
                                for a, c in zip(st, plan)))
        else:
            states.append(agg.update(jnp, st, gids, cap, v, m))
    return states


@_staged("finalize")
def emit_runs_finalize(root, order_root, aggs: List[AggFunc], cap: int,
                       key_bounds, key_dtypes, rows, arg_bits=()):
    """The tail of a grouping by sorted runs, over `sort_rows`' output:
    aggregate states by scans over the runs, a packed word of states at a
    time (scope `agg`; `arg_bits` the arguments' known widths), group keys
    unpacked from the words at the run ends (arithmetic on `cap` gathered
    words — no row gathers), then the root ORDER BY … LIMIT by selection
    (`topn_select`; an ORDER BY without a limit sorts).
    → {keys, states, n_groups[, n_out]} as emit_merge / emit_finalize
    give them."""
    runs = SortedRuns(rows["ends"], rows["n_runs"], cap)
    live_s = rows["words"][0] != jnp.int64(F.DEAD_WORD)
    states = _runs_states(root, aggs, runs, rows["payloads"], live_s,
                          arg_bits)
    keys = [(v, m & runs.slot_live) for v, m in F.unpack_words(
        [runs.at_ends(w) for w in rows["words"]], key_bounds, key_dtypes)]
    out = {"keys": keys, "states": states, "n_groups": runs.n_runs}
    if order_root is None:
        return out
    live = runs.slot_live
    okeys, descs = _order_keys(order_root, aggs, len(keys), keys, states,
                               live)
    with stage("sort"):
        if isinstance(order_root, PhysTopN):
            idx, n_out = F.topn_select(
                okeys, descs, live,
                min(order_root.count + order_root.offset, cap))
        else:
            idx, n_out = F.sort_perm(okeys, descs, live)
    return {"keys": [(v[idx], m[idx]) for v, m in keys],
            "states": [tuple(a[idx] for a in st) for st in states],
            "n_groups": runs.n_runs, "n_out": n_out}


def _perfect_groups(ctx: EvalContext, live, root, cap: int,
                    key_bounds):
    """Stats-informed grouping without sorting: group-key domains are
    known small bounds (dictionary sizes / cached min-max), so the group
    id is a direct packed code and aggregation is pure segment ops —
    the TPU-native analog of the reference's hash table when NDV is low
    (executor/aggregate.go getGroupKey), minus the sort factorize's
    O(n log n) multi-operand bitonic sort. cap == the packed key domain.
    """
    n = live.shape[0]
    keys = [e.eval(ctx) for e in root.group_exprs]
    # packed code: per-key code 0 = NULL (its own group), else 1+v-lo
    gid = jnp.zeros(n, dtype=jnp.int32)
    stride = 1
    cards = []
    for (v, m), (lo, hi) in zip(keys, key_bounds):
        card = hi - lo + 2
        code = jnp.where(jnp.asarray(m),
                         (jnp.clip(jnp.asarray(v), lo, hi) - lo + 1)
                         .astype(jnp.int32),
                         jnp.int32(0))
        gid = gid + code * jnp.int32(stride)
        stride *= card
        cards.append(card)
    gids_raw = jnp.where(live, gid, jnp.int32(cap))
    occupied = seg.segment_sum(
        jnp, jnp.where(live, jnp.int32(1), jnp.int32(0)), gids_raw,
        cap) > 0
    # compact occupied slots to the front (argsort over cap, not rows)
    perm = jnp.argsort(jnp.logical_not(occupied), stable=True)
    n_groups = occupied.sum().astype(jnp.int32)
    inv = jnp.zeros(cap, jnp.int32).at[perm].set(
        jnp.arange(cap, dtype=jnp.int32))
    gids = jnp.where(live, inv[gid], jnp.int32(cap))
    slot_live = jnp.arange(cap, dtype=jnp.int32) < n_groups
    # reconstruct key values from the packed slot code — no row gathers
    key_out = []
    stride = 1
    for (v, m), (lo, hi), card in zip(keys, key_bounds, cards):
        c = (perm // stride) % card
        stride *= card
        vals = (c - 1 + lo).astype(jnp.asarray(v).dtype)
        key_out.append((vals, (c != 0) & slot_live))
    return keys, gids, n_groups, key_out, slot_live


def _rollup_tile(ctx: EvalContext, live, root):
    """Tile the batch columns (nk+1)× along the row axis for WITH ROLLUP
    level replication.  Wide-decimal limb planes are 2-D (limbs, rows),
    so values concatenate along the LAST axis; 1-D masks along axis 0 is
    the same thing."""
    reps = len(root.group_exprs) + 1

    def t(a):
        a = jnp.asarray(a)
        return jnp.concatenate([a] * reps, axis=-1)

    cols = [None if c is None else (t(c[0]), t(c[1]))
            for c in ctx._columns]
    ctx_t = EvalContext(ctx.xp, cols, dictionaries=ctx.dictionaries,
                        prepared=ctx.prepared, on_device=ctx.on_device)
    return ctx_t, t(live)


def _distinct_arg(ctx: EvalContext, live, desc):
    """Evaluate a DISTINCT aggregate's argument tuple → (v, m, vcols).
    Single-arg: the value itself. Multi-arg (COUNT-only — the eligibility
    gates reject anything else): `v` is one combined dense code per row
    via factorize.dense_codes, so equal tuples dedup as one value within
    the batch, and `m` is the AND of the per-arg masks (MySQL skips rows
    where ANY DISTINCT argument is NULL). `vcols` keeps the raw per-arg
    (value, mask) columns for the cross-slab pair output — the combined
    code is batch-local and cannot be compared across slabs."""
    vcols = []
    m = live
    for a in desc.args:
        av, am = a.eval(ctx)
        av = jnp.asarray(av)
        am = jnp.asarray(am) & live
        vcols.append((av, am))
        m = m & am
    if len(vcols) == 1:
        return vcols[0][0], m, vcols
    return F.dense_codes(vcols, live), m, vcols


@_staged("agg")
def agg_states(ctx, live, root, aggs, gids, cap: int, n: int):
    """Per-aggregate partial states over one batch (DISTINCT args dedup
    via factorize.distinct_mask) — shared by single-device and per-shard
    partials."""
    return _agg_states(ctx, live, root, aggs, gids, cap, n)


def _agg_states(ctx, live, root, aggs, gids, cap: int, n: int,
                distinct_first=None, distinct_vals=None, arg_bits=()):
    """One state tuple per aggregate over the batch. Where the shapes
    send slot sums to the matrix unit (`seg.slot_sum_lowering`: `mxu`),
    every state that is a sum of a per-row integer — COUNT, SUM and AVG
    over integers and scaled DECIMALs — comes out of ONE call of
    `seg.slot_sums` for the whole aggregate: a column is evaluated once,
    so `SUM(x)` and `AVG(x)` share their pieces and every aggregate over
    one validity its count. Any other state, and every state of the
    other lowerings, is the aggregate's own `update`. `arg_bits`
    (ops/factorize.KeyBounds) holds per aggregate the width its argument
    is known to have, which its columns are then cut by. Says which
    lowering the traced program took, once a trace: counter
    `tidb_tpu_slot_sum_programs_total{lowering=}` and tag `slot_sums` =
    `<lowering>:<n>` (the state arrays, a reduction each) or, of a
    contraction, `mxu:<rows>/<rows at whole width>` (the piece rows it
    holds, and those it would hold knowing no width) on the span that
    covers the trace; and of a contraction counter
    `tidb_tpu_slot_sum_columns_total{range=bounded|whole}`, its distinct
    summed values by whether a width was known."""
    evaluated = {}      # an argument → its (values, validity)
    inputs = []
    for ai, (agg, desc) in enumerate(zip(aggs, root.aggs)):
        if desc.distinct and desc.args and distinct_vals is not None \
                and ai in distinct_vals:
            v, m = distinct_vals[ai]     # evaluated once by emit_agg
        elif desc.distinct and desc.args:
            v, m, _ = _distinct_arg(ctx, live, desc)
        elif desc.args:
            # a plain column is ONE pair of arrays however many aggregates
            # name it (a computed argument is not compared: a parameter's
            # repr leaves its value out)
            arg = desc.args[0]
            key = arg.index if isinstance(arg, ColumnRef) else id(arg)
            if key not in evaluated:
                v, m = arg.eval(ctx)
                evaluated[key] = (jnp.asarray(v), jnp.asarray(m) & live)
            v, m = evaluated[key]
        else:
            v = jnp.zeros(n, dtype=jnp.int64)
            m = live
        if desc.distinct and desc.args:
            # keep only the first (group, value) occurrence
            if distinct_first is not None and ai in distinct_first:
                m = m & distinct_first[ai]
            else:
                m = m & F.distinct_mask(gids, v, m, live)
        inputs.append((v, m))
    lowering = seg.slot_sum_lowering(jnp, n, cap)
    widths = arg_bits or [None] * len(aggs)
    plans = [agg.row_sums(jnp, v, m, bits) if lowering == "mxu" else None
             for agg, (v, m), bits in zip(aggs, inputs, widths)]
    columns = [c for plan in plans if plan for c in plan if c is not None]
    if lowering == "mxu" and not columns:
        lowering = "masked"             # MIN/MAX and their like alone
    states = _fill_states(aggs, plans, inputs, gids, cap,
                          seg.slot_sums(jnp, columns, gids, cap))
    REGISTRY.inc("tidb_tpu_slot_sum_programs_total", {"lowering": lowering})
    if lowering != "mxu":
        timeline.tag(
            slot_sums=f"{lowering}:{sum(len(st) for st in states)}")
        return states
    whole = [c for agg, plan, (v, m) in zip(aggs, plans, inputs) if plan
             for c in agg.row_sums(jnp, v, m) if c is not None]
    timeline.tag(slot_sums=f"mxu:{seg.slot_sum_pieces(columns)}"
                           f"/{seg.slot_sum_pieces(whole, True)}")
    summed = {id(v): "whole" if bits is None else "bounded"
              for plan, (v, _m), bits in zip(plans, inputs, widths)
              if plan and any(c and c.values is not None for c in plan)}
    for known in summed.values():
        REGISTRY.inc("tidb_tpu_slot_sum_columns_total", {"range": known})
    return states


# ---------------------------------------------------------------------------
# Window root
# ---------------------------------------------------------------------------


@_staged("window")
def emit_window(ctx: EvalContext, live, root):
    """Window root on device: one lax.sort per distinct (partition, order)
    spec, then the cumulative/segment primitives of ops/window.py traced
    with jnp (the whole-column reformulation of executor/window.go).
    → {cols, live} with the window outputs appended to the child columns."""
    n_child = len(root.children[0].schema)
    in_cols = [ctx.column(i) for i in range(n_child)]
    out_cols = emit_window_cols(ctx, live, root, in_cols)
    return {"cols": [(jnp.asarray(v), jnp.asarray(m))
                     for v, m in out_cols], "live": live}


@_staged("window")
def emit_window_cols(ctx: EvalContext, live, root, in_cols):
    """The traced window computation proper → the child's column list
    (None placeholders preserved) with one appended (value, mask) column
    per window spec. Shared by the window-ROOT emit above and the
    interior-window case of TreeProgram._emit, where the appended
    columns feed the operator above in the same trace."""
    n = live.shape[0]
    out_cols = list(in_cols)
    layouts = {}
    for d in root.wdescs:
        lkey = repr((d.partition, d.order, d.descs))
        layout = layouts.get(lkey)
        if layout is None:
            pkeys = [e.eval(ctx) for e in d.partition]
            okeys = [e.eval(ctx) for e in d.order]
            perm, _ = F.sort_perm(pkeys + okeys,
                                  [False] * len(pkeys) + list(d.descs),
                                  live)
            lives_s = jnp.take(live, perm)
            first = jnp.zeros(n, dtype=bool).at[0].set(True)

            def flags(cols):
                out = first | jnp.concatenate(
                    [jnp.zeros(1, dtype=bool),
                     lives_s[1:] != lives_s[:-1]])
                for v, m in cols:
                    vs = jnp.take(jnp.asarray(v), perm)
                    ms = jnp.take(jnp.asarray(m), perm)
                    # NULL slots hold garbage values: neutralize so all
                    # NULLs form ONE group (SQL GROUP/PARTITION NULLs)
                    vs = jnp.where(ms, vs, jnp.zeros_like(vs))
                    out = out | jnp.concatenate(
                        [jnp.zeros(1, dtype=bool),
                         (vs[1:] != vs[:-1]) | (ms[1:] != ms[:-1])])
                return out

            pstart = flags(pkeys)
            peerstart = flags(pkeys + okeys) if okeys else pstart
            layout = (perm, pstart, peerstart)
            layouts[lkey] = layout
        perm, pstart, peerstart = layout
        v, m = _window_value(ctx, live, d, n, perm, pstart, peerstart)
        back_v = jnp.zeros(n, dtype=v.dtype).at[perm].set(v)
        back_m = jnp.zeros(n, dtype=bool).at[perm].set(m)
        out_cols.append((back_v, back_m & live))
    return out_cols


def _window_value(ctx, live, d, n, perm, pstart, peerstart):
    vals = valid = fill = None
    if d.args:
        v, m = d.args[0].eval(ctx)
        vals = jnp.take(jnp.asarray(v), perm)
        valid = jnp.take(jnp.asarray(m) & live, perm)
    elif d.name not in ("row_number", "rank", "dense_rank"):
        vals = jnp.zeros(n, dtype=jnp.int64)        # COUNT(*)
        valid = jnp.take(live, perm)
    if d.name in ("lag", "lead"):
        if d.default is not None and d.default.value is not None:
            fv = d.args[0].ftype.encode_value(d.default.value)
            fill = (jnp.full(n, fv, dtype=vals.dtype),
                    jnp.ones(n, dtype=bool))
        else:
            fill = (jnp.zeros(n, dtype=vals.dtype),
                    jnp.zeros(n, dtype=bool))
    if d.name == "avg" and d.args and \
            d.args[0].ftype.kind is TypeKind.DECIMAL:
        from tidb_tpu.ops.jax_env import device_float_dtype
        vals = vals.astype(device_float_dtype()) / \
            d.args[0].ftype.decimal_multiplier
    frame = getattr(d, "frame", None)
    range_key = None
    if frame is not None and frame[0] == "range":
        kv, km = d.order[0].eval(ctx)
        range_key = (jnp.take(jnp.asarray(kv), perm),
                     jnp.take(jnp.asarray(km) & live, perm),
                     bool(d.descs[0]))
    return W.compute(jnp, d.name, vals, valid, pstart, peerstart,
                     bool(d.order), d.offset, fill, frame=frame,
                     range_key=range_key)


@_staged("partition")
def emit_partition(arrays: Sequence, dest, live, n_shards: int,
                   bucket_cap: int):
    """Traced per-rank bucket scatter — stage 1 of the staged exchange.

    The scatter half of parallel/collective.exchange() with the in-trace
    all_to_all removed: ONE rank's rows land in `n_shards` fixed-capacity
    destination buckets, ready for a device→host checkpoint and
    host-mediated routing (collective.route_buckets). Identical rank /
    slot / drop arithmetic to exchange(), so the staged path inherits the
    monolithic path's exact-need overflow contract: rows past bucket_cap
    are dropped and `need` (= counts.max()) reports the true per-bucket
    requirement for the capacity ladder's ONE exact resize.

    arrays: per-row payload [(N,)...]; dest (N,) int32; live (N,) bool.
    → (bufs [(n_shards*bucket_cap,)...], sent_live, counts (n_shards,),
       need ()). Within bucket d the prefix [0:counts[d]] is contiguous
    live rows (rows are ranked densely per destination)."""
    n = dest.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    d = jnp.where(live, dest, jnp.int32(n_shards))  # dead rows → no bucket
    sorted_d, sorted_row = lax.sort((d, iota), num_keys=1)
    first_of_d = jax.ops.segment_min(jnp.arange(n, dtype=jnp.int32),
                                     sorted_d, num_segments=n_shards + 1)
    rank_sorted = jnp.arange(n, dtype=jnp.int32) - \
        jnp.take(first_of_d, jnp.clip(sorted_d, 0, n_shards))
    rank = jnp.zeros(n, dtype=jnp.int32).at[sorted_row].set(rank_sorted)
    counts = jax.ops.segment_sum(jnp.ones(n, dtype=jnp.int32), d,
                                 num_segments=n_shards + 1)[:n_shards]
    slot = d * bucket_cap + rank
    ok = live & (rank < bucket_cap)
    slot = jnp.where(ok, slot, n_shards * bucket_cap)  # OOB → dropped
    total = n_shards * bucket_cap
    sent_live = jnp.zeros(total, dtype=bool).at[slot].set(ok, mode="drop")
    bufs = []
    for a in arrays:
        a = jnp.asarray(a)
        bufs.append(jnp.zeros(total, dtype=a.dtype).at[slot].set(
            jnp.where(ok, a, jnp.zeros((), dtype=a.dtype)), mode="drop"))
    return bufs, sent_live, counts, counts.max()


def emit_batched(partial_fn, name: str):
    """Same-plan micro-batching entry: vmap one fragment's traced
    per-slab partial over a LEADING MEMBER AXIS of the prepared inputs
    (each member = one queued statement's stacked parameters), with the
    slab columns and row count broadcast unmapped. XLA compiles ONE
    program whose every output leaf grows a leading member axis; the
    micro-batcher (executor/microbatch.py) slices that axis back out,
    one lane per waiting session. → the jitted batched callable
    `(cols, n_rows, stacked_preps) -> outputs`, compiled under `name`."""
    from tidb_tpu.ops.jax_env import named_jit

    def batched(cols, n_rows, stacked_preps):
        return jax.vmap(partial_fn,
                        in_axes=(None, None, 0))(cols, n_rows,
                                                 stacked_preps)

    return named_jit(batched, name)


# ---------------------------------------------------------------------------
# delta slab and row-liveness masks — what a write does on the device
# ---------------------------------------------------------------------------
#
# A delta generation (executor/delta.py) never moves a resident row. The
# appended rows of a table live RAW (no compressed layout, so no range,
# order or dictionary-width invariant of the base can be broken) in one
# delta slab of its own capacity, which a write extends by a scatter of
# just the new rows; a deleted row clears one bit of its slab's liveness
# mask. Both are functional updates (the old generation's arrays stay
# valid for the readers that hold them), both are programs of their own,
# keyed by shapes alone — no table data and no generation in a trace —
# and traced under a `jax.named_scope` (`delta_merge`, `tombstone`).

DELTA_SCOPES = ("delta_merge", "tombstone")
_DELTA_PROGRAMS: dict = {}


def delta_program(kind: str, key: tuple, build, **jit_kwargs):
    """The jitted program `<kind>_<sig8 of key>`, built once a process:
    `key` holds every constant `build()`'s function closes over (and
    stands for `jit_kwargs`, e.g. a donated argument)."""
    from tidb_tpu.ops.jax_env import named_jit, program_name
    fn = _DELTA_PROGRAMS.get((kind, key))
    if fn is None:
        fn = _DELTA_PROGRAMS[(kind, key)] = named_jit(
            build(), program_name(kind, repr(key)), **jit_kwargs)
    return fn


def emit_delta_alloc(specs, cap: int):
    """Empty raw delta slabs, made on the device (nothing crosses PCIe):
    `specs` = [(leading shape, dtype name)] per column → [(vals, mask)]."""
    specs = tuple((tuple(lead), str(dt)) for lead, dt in specs)

    def build():
        def _alloc():
            with jax.named_scope("delta_merge"):
                return [(jnp.zeros(lead + (cap,), dtype=dt),
                         jnp.zeros(cap, dtype=bool)) for lead, dt in specs]
        return _alloc
    return delta_program("delta_merge", ("alloc", specs, cap), build)()


def emit_delta_append(slab, chunk_vals, chunk_mask, offset: int, n: int,
                      cap: int):
    """Write `n` appended rows (host arrays padded to a power-of-two
    bucket) into a raw delta slab at row `offset` → the new (vals, mask).
    A scatter with the padding's indices out of range, so the rows beyond
    `n` are dropped and a write near the capacity cannot shift."""
    bucket = int(chunk_mask.shape[0])
    lead = tuple(chunk_vals.shape[:-1])
    key = ("append", lead, str(chunk_vals.dtype), bucket, cap)

    def build():
        def _append(v, m, cv, cm, off, n_new):
            with jax.named_scope("delta_merge"):
                i = jnp.arange(bucket, dtype=jnp.int32)
                idx = jnp.where(i < n_new, off + i, jnp.int32(cap))
                return (v.at[..., idx].set(cv, mode="drop"),
                        m.at[idx].set(cm, mode="drop"))
        return _append
    return delta_program("delta_merge", key, build)(
        slab[0], slab[1], chunk_vals, chunk_mask, jnp.int32(offset),
        jnp.int32(n))


# ---------------------------------------------------------------------------
# The folded slab layout (how a column's stack holds a slab's 1-D leaf)
# ---------------------------------------------------------------------------

LANES = 128     # the minor dimension of a device tile


def folded(shape: tuple) -> tuple:
    """The shape a slab's leaf has inside its column's stack. The device
    tiles an array's two minor dimensions: stacked as (slabs, rows), the
    SLAB axis of a 1-D leaf would lie inside a tile — padded to 8 (six
    slabs take the HBM of eight) and every slab read with a stride. Folded
    to (rows / 128, 128) the slab axis stays outside, a slab is as
    contiguous as it was alone, and slab ↔ its 1-D form is a bitcast
    (compiled for the v5e: `tests/test_tpu_compile.py`). A length that is
    no multiple of 128 (a small table's) is padded up to one: its slab is
    the fold's first rows."""
    if len(shape) != 1:
        return shape
    return (-(-shape[0] // LANES), LANES)


def fold(a):
    """One slab's leaf as its column's stack holds it (`folded`)."""
    if a.ndim != 1:
        return a
    pad = -a.shape[0] % LANES
    return (jnp.pad(a, (0, pad)) if pad else a).reshape(folded(a.shape))


def unfold(a, shape: tuple):
    """A slab of a stack → the leaf in its own shape (a bitcast, and a
    cut where the fold was padded)."""
    if len(shape) != 1:
        return a
    a = a.reshape(-1)
    return a if a.shape[0] == shape[0] else a[:shape[0]]


def emit_alive_init(n_live: int, cap: int):
    """The liveness mask of a slab that has only a live prefix."""

    def build():
        def _init(n):
            with jax.named_scope("tombstone"):
                return jnp.arange(cap, dtype=jnp.int32) < n
        return _init
    return delta_program("tombstone", ("init", cap), build)(
        jnp.int32(n_live))


def emit_alive_stack(counts, cap: int):
    """The liveness masks of base slabs that have only live prefixes
    (`counts` rows each, a host int32 vector), made as ONE array the way
    `device_cache.SlabColumn` stacks a column: slabs × a slab's rows
    folded in two."""
    rows, lanes = folded((cap,))

    def build():
        def _init(n):
            with jax.named_scope("tombstone"):
                pos = jnp.arange(rows * lanes,
                                 dtype=jnp.int32).reshape(rows, lanes)
                return pos[None] < n[:, None, None]
        return _init
    return delta_program("tombstone", ("init", cap, len(counts)), build)(
        jnp.asarray(list(counts), dtype=jnp.int32))


def emit_alive_update(alive, born, dead, cap: int, stacked: bool = False):
    """A slab's liveness mask with rows `born` set and rows `dead`
    cleared (int32 host arrays padded with `cap`, which a scatter
    drops) → the new mask; the old one stays as it was. `stacked`: `alive`
    is the base slabs' masks as ONE array (`device_cache.SlabColumn`:
    slabs × a slab's rows folded in two) and the rows are positions in the
    whole base (padded with its size): one rewrite whatever slabs they
    fall in."""
    key = ("update", int(born.shape[0]), int(dead.shape[0]), cap) + \
        (tuple(alive.shape) if stacked else ())

    def build():
        def _update(a, b, d):
            with jax.named_scope("tombstone"):
                if stacked:
                    # (a slab's rows lie folded in two, `cap` of them
                    # from the fold's start: `fold`)
                    c = a.shape[2]
                    b, d = ((p // cap, p % cap // c, p % cap % c)
                            for p in (b, d))
                return a.at[b].set(True, mode="drop") \
                        .at[d].set(False, mode="drop")
        return _update
    return delta_program("tombstone", key, build)(alive, born, dead)
