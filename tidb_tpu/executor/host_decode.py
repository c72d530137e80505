"""Device results → host Chunks: the decode every device driver ends in
(dictionary codes back to strings, padded slots cut to their live rows),
the host-side merges of what the device hands out in pieces (aggregate
passes, DISTINCT pair sets) and the host re-order of a small result.
Numpy only; a leaf of the executor.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from tidb_tpu.chunk import Chunk, Column
from tidb_tpu.executor import empty_chunk
from tidb_tpu.executor.eligibility import FragmentFallback
from tidb_tpu.executor.hash_agg import factorize_columns
from tidb_tpu.expression import ColumnRef, Expression
from tidb_tpu.expression.aggfuncs import build_agg
from tidb_tpu.expression.runner import eval_on_chunk
from tidb_tpu.planner.physical import (PhysHashAgg, PhysLimit,
                                       PhysProjection, PhysTableScan,
                                       PhysTopN, PhysicalPlan)
from tidb_tpu.types import FieldType


def agg_chunk(ctx, schema, root: PhysHashAgg, out, dicts, n_final,
              distinct_pairs=None, host_tree=None) -> Chunk:
    """An aggregate's merged keys and states (`out`, on the device; or
    `host_tree`, already fetched) → its result rows, decoded."""
    if host_tree is not None:
        # keys/states already came back WITH the flag fetch (small
        # group caps piggyback on round trip #1); slice the padding
        # off host-side
        hk, hs = host_tree
        host_keys = [(np.asarray(k)[:n_final], np.asarray(m)[:n_final])
                     for k, m in hk]
        host_states = [tuple(np.asarray(a)[:n_final] for a in st)
                       for st in hs]
    else:
        # slice ON DEVICE, fetch EVERYTHING in one device_get:
        # transfers n_final rows per array in one round trip
        dev_tree = (
            [(k[:n_final], m[:n_final]) for k, m in out["keys"]],
            [tuple(a[:n_final] for a in st) for st in out["states"]],
        )
        host_keys, host_states = ctx.phases.fetch(dev_tree)
    if distinct_pairs:
        # multi-slab DISTINCT: the device-merged distinct states
        # deduped only within each slab — recompute them from the
        # cross-slab-deduped pair sets
        over = merge_distinct_states(root, host_keys, distinct_pairs,
                                      n_final)
        host_states = [over.get(ai, st)
                       for ai, st in enumerate(host_states)]
    cols: List[Column] = []
    for kc, e in enumerate(root.group_exprs):
        ft = schema[kc]
        v, m = host_keys[kc]
        cols.append(decode_col(ft, v, m, expr_dict(e, dicts)))
    for agg, st in zip([build_agg(d) for d in root.aggs], host_states):
        v, m = agg.final(np, st)
        cols.append(decode_col(agg.ftype, np.asarray(v),
                                np.asarray(m, dtype=bool), None))
    return Chunk(cols)


def merge_tree_agg_passes(ctx, schema, root: PhysHashAgg, pass_outs,
                          inp_dicts) -> Chunk:
    """Host-side cross-pass group merge: concatenate each pass's live
    (key, state) slots, re-group by key tuple, AggFunc.merge with
    xp=numpy (update=merge symmetry — the same segment op either
    way)."""
    aggs = [build_agg(d) for d in root.aggs]
    n_keys = len(root.group_exprs)
    if n_keys and getattr(root, "rollup", False):
        n_keys += 1     # device partials carry a grouping-level column
    key_parts: List[List] = [[] for _ in range(n_keys)]
    state_parts: List[List] = [[] for _ in aggs]
    for got in pass_outs:
        ng = int(np.asarray(got["ng"]))
        if ng == 0:
            continue
        for kc in range(n_keys):
            v, m = got["keys"][kc]
            key_parts[kc].append((np.asarray(v)[:ng],
                                  np.asarray(m)[:ng]))
        for ai, st in enumerate(got["states"]):
            state_parts[ai].append(
                tuple(np.asarray(a)[:ng] for a in st))
    if n_keys and not key_parts[0]:
        return empty_chunk(schema)
    key_cols = [(np.concatenate([v for v, _ in parts]),
                 np.concatenate([m for _, m in parts]))
                for parts in key_parts]
    if n_keys:
        n_rows = key_cols[0][0].shape[0]
        # vectorized cross-pass group index (NULLs group together) —
        # the same sort-based factorize the CPU hash agg uses
        gids, n_final, rep = factorize_columns(key_cols)
    else:
        # global agg: every pass contributes exactly one state row
        n_rows = sum(p[0].shape[0] for p in state_parts[0]) \
            if state_parts and state_parts[0] else 0
        gids = np.zeros(n_rows, dtype=np.int64)
        n_final = 1
    merged_states = []
    for agg, parts in zip(aggs, state_parts):
        if parts:
            partial = tuple(
                np.concatenate([p[c] for p in parts], axis=0)
                for c in range(len(parts[0])))
        else:
            partial = agg.init(np, 0)
        st = agg.init(np, n_final)
        merged_states.append(
            agg.merge(np, st, gids, n_final, partial))
    # representative key row per group (factorize's first occurrence)
    keys_out = []
    if n_keys:
        for kc in range(n_keys):
            v, m = key_cols[kc]
            keys_out.append((v[rep], m[rep]))
    out = {"keys": keys_out, "states": merged_states}
    return agg_chunk(ctx, schema, root, out, inp_dicts, max(n_final, 1))


def cols_chunk(root, host_cols, dicts) -> Chunk:
    child_types = [ft for ft in root.schema.field_types]
    out = []
    for ci, ((v, m), ft) in enumerate(zip(host_cols, child_types)):
        out.append(decode_col(ft, np.asarray(v), np.asarray(m),
                               positional_dict(root, ci, dicts)))
    return Chunk(out)


def expr_dict(e: Expression, dicts) -> Optional[np.ndarray]:
    if isinstance(e, ColumnRef):
        return dicts.get(e.index)
    return None


def positional_dict(node: PhysicalPlan, out_idx: int, dicts
                     ) -> Optional[np.ndarray]:
    """Dictionary for output column `out_idx` of a non-agg root: identity
    through Selection/TopN/Sort; via ColumnRef for Projection outputs."""
    cur = node
    idx = out_idx
    while True:
        if isinstance(cur, PhysTableScan):
            return dicts.get(idx)
        if isinstance(cur, PhysProjection):
            e = cur.exprs[idx]
            if isinstance(e, ColumnRef):
                idx = e.index
            else:
                return None
        cur = cur.children[0] if cur.children else None
        if cur is None:
            return None


def host_run_bounds(cols) -> Tuple[np.ndarray, np.ndarray]:
    """Lexsort rows of [(values, valid), ...] → (order, first_of_run mask
    over the sorted order). NULL slots canonicalize so all NULLs in a
    column compare equal (the host mirror of ops/factorize.py)."""
    arrays: List[np.ndarray] = []
    for v, m in cols:
        v = np.asarray(v)
        m = np.asarray(m)
        arrays.append(np.where(m, v, np.zeros((), dtype=v.dtype)))
        arrays.append(m)
    n = len(arrays[0]) if arrays else 0
    order = np.lexsort(arrays[::-1]) if arrays else np.arange(0)
    first = np.zeros(n, dtype=bool)
    if n:
        first[0] = True
        for a in arrays:
            sa = a[order]
            first[1:] |= sa[1:] != sa[:-1]
    return order, first


def host_group_index(final_cols, query_cols) -> np.ndarray:
    """Map each query row's key tuple to its row index in final_cols
    (−1 when absent). Vectorized via one shared lexsort — no Python dict,
    so cross-slab DISTINCT merges scale to millions of pairs."""
    nf = len(final_cols[0][0]) if final_cols else 0
    nq = len(query_cols[0][0]) if query_cols else 0
    if not final_cols:
        return np.zeros(nq, dtype=np.int64)
    both = [(np.concatenate([np.asarray(fv), np.asarray(qv)]),
             np.concatenate([np.asarray(fm), np.asarray(qm)]))
            for (fv, fm), (qv, qm) in zip(final_cols, query_cols)]
    order, first = host_run_bounds(both)
    gid_sorted = np.cumsum(first) - 1
    gid = np.empty(nf + nq, dtype=np.int64)
    gid[order] = gid_sorted
    slot_of = np.full(int(gid_sorted[-1]) + 1 if len(gid_sorted) else 1,
                      -1, dtype=np.int64)
    slot_of[gid[:nf]] = np.arange(nf)
    return slot_of[gid[nf:]]


def merge_distinct_states(root, host_keys, distinct_pairs, n_final):
    """Cross-slab DISTINCT merge: concatenate per-slab pair sets, dedup
    globally (lexsort runs), map pairs onto the final merged groups, and
    recompute each distinct aggregate's state with the numpy side of the
    xp-generic agg framework (the distinct-partials split of
    aggfuncs/func_sum.go:49-59). → {agg_index: state_tuple}."""
    nk = len(root.group_exprs)
    out = {}
    for ai, slabs in distinct_pairs.items():
        na = max(1, len(root.aggs[ai].args))
        cols = []
        for c in range(nk + na):
            v = np.concatenate([np.asarray(s[c][0]) for s in slabs])
            m = np.concatenate([np.asarray(s[c][1]) for s in slabs])
            cols.append((v, m))
        order, first = host_run_bounds(cols)
        uniq = np.zeros(len(order), dtype=bool)
        uniq[order] = first
        vv = cols[nk][0]
        vm = np.ones(len(order), dtype=bool)
        for _av, am in cols[nk:]:
            vm = vm & np.asarray(am)     # any NULL arg → row never counts
        keep = uniq & vm
        if nk:
            gidx = host_group_index(
                host_keys, [(np.asarray(v)[keep], np.asarray(m)[keep])
                            for v, m in cols[:nk]])
            ok = gidx >= 0   # every pair's group exists in the final set
            gids = np.where(ok, gidx, 0).astype(np.int32)
        else:
            ok = np.ones(int(keep.sum()), dtype=bool)
            gids = np.zeros(int(keep.sum()), dtype=np.int32)
        agg = build_agg(root.aggs[ai])
        st = agg.init(np, n_final)
        out[ai] = agg.update(np, st, gids, n_final,
                             np.asarray(vv)[keep],
                             np.asarray(vm)[keep] & ok)
    return out


def compact_decode(cols_vm, live_mask, ftypes, dicts_root) -> Chunk:
    """Compact padded (values, validity) columns by a live mask and decode
    them into a host Chunk (shared by the single-chip and distributed
    row/window-root result paths)."""
    idx = np.nonzero(np.asarray(live_mask))[0]
    return Chunk([decode_col(ft, np.asarray(v)[idx], np.asarray(m)[idx],
                              dicts_root.get(ci))
                  for ci, ((v, m), ft) in enumerate(zip(cols_vm, ftypes))])


def topn_slice(chunk: Chunk, root) -> Chunk:
    if isinstance(root, (PhysTopN, PhysLimit)):
        lo = min(root.offset, chunk.num_rows)
        hi = min(root.offset + root.count, chunk.num_rows)
        return chunk.slice(lo, hi)
    return chunk


def decode_col(ft: FieldType, vals: np.ndarray, mask: np.ndarray,
                dictionary: Optional[np.ndarray]) -> Column:
    if ft.is_varlen:
        if dictionary is None:
            if not np.asarray(mask, dtype=bool).any():
                # unused placeholder column: all-NULL is fine
                return Column.all_null(ft, len(vals))
            raise FragmentFallback("string column without dictionary", reason="string-dict")
        neg = vals < 0
        if neg.any():
            mask = mask & ~neg
        if len(dictionary):
            decoded = dictionary[np.clip(vals, 0, len(dictionary) - 1)]
            decoded = np.asarray(decoded, dtype=object)
        else:
            decoded = np.full(len(vals), "", dtype=object)
        vals = decoded
    elif vals.dtype != ft.np_dtype:
        vals = vals.astype(ft.np_dtype)
    mask = np.asarray(mask, dtype=bool)
    return Column(ft, vals, None if mask.all() else mask.copy())


def host_order(chunk: Chunk, root, schema) -> Chunk:
    """k-way candidate merge for multi-slab TopN: re-sort the (small)
    concatenated candidates on host with MySQL NULL ordering (NULLs first
    ASC, last DESC)."""
    lex_keys: List[np.ndarray] = []   # np.lexsort: LAST key is primary
    for e, desc in zip(root.by, root.descs):
        if isinstance(e, ColumnRef):
            col = chunk.columns[e.index]
        else:
            col = eval_on_chunk([e], chunk).columns[0]
        vals = col.values
        valid = col.valid_mask()
        if not valid.all():
            # neutralize masked-out garbage so ordering among NULL-key rows
            # falls through to the next ORDER BY key (matches CPU engine)
            fill = "" if vals.dtype == object else np.zeros(1, vals.dtype)[0]
            vals = np.where(valid, vals, fill)
        if vals.dtype == object:
            ranks = {v: i for i, v in
                     enumerate(sorted({str(x) for x in vals}))}
            vals = np.array([ranks[str(v)] for v in vals], dtype=np.int64)
        if desc:
            val_key = -vals.astype(np.float64) if vals.dtype.kind == "f" \
                else ~vals.astype(np.int64)
            null_key = ~valid            # NULLs last
        else:
            val_key = vals
            null_key = valid             # NULLs first (False < True)
        # primary-first ORDER BY list → reversed for lexsort; within one
        # column the null flag outranks the value
        lex_keys = [val_key, null_key] + lex_keys
    order = np.lexsort(lex_keys) if lex_keys else np.arange(chunk.num_rows)
    return chunk.take(order)
