"""Index access paths: point get + index range scan.

Ref: executor/point_get.go, executor/distsql.go:157 (IndexReader). The
reference reads index key ranges from a B-tree-ordered KV store; the
columnar TPU-first analog is a SORTED VIEW over the immutable snapshot:
first use of an index on a table version argsorts the key column once
(O(n log n), cached by TableData identity exactly like the HBM device
cache), after which every range probe is two binary searches plus a
row gather — the same asymptotics as an index seek, with no extra
write-path maintenance (append-only storage rebuilds lazily).

On the timeline (lane `index`): `index.build` once a table version and
index (`table`, `index`, `rows`: the live view gathered and its key
sorted) and `index.probe` once an executor (`ranges`, `rows` out: the
binary searches, the gather, the residual filters). Always-on counters:
`tidb_tpu_index_builds_total{table}`, `tidb_tpu_index_probes_total`,
`tidb_tpu_index_rows_total`.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import List, Optional, Tuple

import numpy as np

from tidb_tpu.chunk import Chunk
from tidb_tpu.executor import MaterializingExec, empty_chunk
from tidb_tpu.expression.runner import filter_mask
from tidb_tpu.planner.ranger import Range
from tidb_tpu.util import timeline
from tidb_tpu.util.observability import REGISTRY
from tidb_tpu.executor.scan import align_chunk_to_schema

MAX_CACHED_INDEXES = 16


def _build_span(table_info, index: str):
    """Around one build of a sorted view (a cache miss): counted, and on
    the timeline with the rows it sorted (the builder tags them)."""
    REGISTRY.inc("tidb_tpu_index_builds_total", {"table": table_info.name})
    return timeline.span("index.build", "index", table=table_info.name,
                         index=index)


class SortedIndex:
    """Sorted view of one column over a table snapshot, plus the
    concatenated live-row view the positions index into (cached together
    so a point-get is two binary searches + a tiny gather, not a
    full-table rematerialization per query)."""

    __slots__ = ("td", "sorted_vals", "sorted_pos", "null_pos", "n_rows",
                 "view")

    def __init__(self, td, sorted_vals, sorted_pos, null_pos, n_rows,
                 view):
        self.td = td
        self.sorted_vals = sorted_vals   # non-NULL values ascending
        self.sorted_pos = sorted_pos     # row position per sorted value
        self.null_pos = null_pos         # positions of NULL rows
        self.n_rows = n_rows
        self.view = view                 # Chunk of live rows (aligned)

    def probe(self, ranges: List[Range]) -> np.ndarray:
        """→ sorted row positions matching any range."""
        hits = []
        for r in ranges:
            if r.include_null:
                hits.append(self.null_pos)
                continue
            hit = _range_window(self.sorted_vals, self.sorted_pos, 0,
                                len(self.sorted_vals), r)
            if hit is not None:
                hits.append(hit)
        return _merge_hits(hits)


def _range_window(sorted_vals: np.ndarray, pos: np.ndarray, lo: int,
                  hi: int, r: Range) -> Optional[np.ndarray]:
    """Row positions of one value Range within sorted_vals[lo:hi]."""
    l2 = lo
    if r.lo is not None:
        l2 = lo + int(np.searchsorted(
            sorted_vals[lo:hi], r.lo, side="left" if r.lo_incl
            else "right"))
    h2 = hi
    if r.hi is not None:
        h2 = lo + int(np.searchsorted(
            sorted_vals[lo:hi], r.hi, side="right" if r.hi_incl
            else "left"))
    return pos[l2:h2] if h2 > l2 else None


def _merge_hits(hits: List[np.ndarray]) -> np.ndarray:
    if not hits:
        return np.empty(0, dtype=np.int64)
    out = np.concatenate(hits) if len(hits) > 1 else hits[0]
    return np.sort(out, kind="stable")     # storage row order


class PrefixSortedIndex:
    """Lexsorted view over an index column PREFIX (detacher.go's
    multi-column ranges): probe narrows [lo, hi) level by level with two
    binary searches per consumed column. NULLs at a level sort after that
    level's values (filled with the level's max value), so candidate
    windows may over-approximate — callers re-verify with the original
    predicates, which keeps sentinel collisions harmless."""

    __slots__ = ("td", "arrs", "pos", "view", "cols")

    def __init__(self, td, arrs, pos, view, cols):
        self.td = td
        self.arrs = arrs               # per-level sorted value arrays
        self.pos = pos                 # row position per sorted slot
        self.view = view
        self.cols = cols

    def probe(self, prefix_vals: List, ranges: List[Range]) -> np.ndarray:
        lo, hi = 0, len(self.pos)
        for lev, v in enumerate(prefix_vals):
            a = self.arrs[lev]
            lo2 = lo + int(np.searchsorted(a[lo:hi], v, side="left"))
            hi2 = lo + int(np.searchsorted(a[lo:hi], v, side="right"))
            lo, hi = lo2, hi2
            if lo >= hi:
                return np.empty(0, dtype=np.int64)
        a = self.arrs[len(prefix_vals)]
        hits = []
        for r in ranges:
            hit = _range_window(a, self.pos, lo, hi, r)
            if hit is not None:
                hits.append(hit)
        return _merge_hits(hits)


_CACHE: "OrderedDict[Tuple, SortedIndex]" = OrderedDict()
_PREFIX_CACHE: "OrderedDict[Tuple, PrefixSortedIndex]" = OrderedDict()
# live view shared across every index of one table snapshot (a wide table
# with 3 indexes must not hold 3 copies of its rows)
_VIEW_CACHE: "OrderedDict[Tuple, Tuple]" = OrderedDict()

# host-side caches shared across connection threads: the lock covers the
# dict operations only (index builds run outside it and commit
# last-writer-wins — builds are deterministic over the same snapshot)
_LOCK = timeline.named_lock("index_views")


def clear():
    with _LOCK:
        _CACHE.clear()
        _PREFIX_CACHE.clear()
        _VIEW_CACHE.clear()


def _fill_nulls(vals: np.ndarray, valid: np.ndarray):
    """NULL slots → the level's max value so the lexsorted array stays
    monotonic (collisions are resolved by caller-side re-verification)."""
    if valid.all():
        return vals
    if vals.dtype == object:
        filler = max((str(v) for v in vals[valid]), default="")
        out = np.array([str(v) if ok else filler
                        for v, ok in zip(vals, valid)], dtype=object)
        return out
    filler = vals[valid].max() if valid.any() else vals.dtype.type(0)
    return np.where(valid, vals, filler)


def get_prefix_index(ctx, table_id: int, col_idxs, table_info
                     ) -> PrefixSortedIndex:
    cacheable = getattr(ctx, "txn", None) is None
    td = ctx.snapshot.table_data(table_id) if cacheable else None
    store = getattr(ctx.snapshot, "store", None) if cacheable else None
    key = (id(store), table_id, tuple(col_idxs)) if cacheable else None
    with _LOCK:
        ent = _PREFIX_CACHE.get(key) if cacheable else None
        if ent is not None and ent.td is td and \
                len(ent.view.columns) == len(table_info.columns):
            _PREFIX_CACHE.move_to_end(key)
            return ent
    names = ",".join(table_info.columns[ci].name for ci in col_idxs)
    with _build_span(table_info, names):
        view = _live_view(ctx, table_id, table_info, cacheable, td, store)
        ctx.check_killed()
        keys = []
        for ci in reversed(list(col_idxs)):  # np.lexsort: LAST is primary
            col = view.columns[ci]
            keys.append(_fill_nulls(col.values, col.valid_mask()))
        order = np.lexsort(keys) if view.num_rows else \
            np.empty(0, dtype=np.int64)
        arrs = [k[order] for k in reversed(keys)]
        ent = PrefixSortedIndex(td, arrs, order.astype(np.int64), view,
                                tuple(col_idxs))
        timeline.tag(rows=view.num_rows)
    if cacheable:
        with _LOCK:
            _PREFIX_CACHE[key] = ent
            while len(_PREFIX_CACHE) > MAX_CACHED_INDEXES:
                _PREFIX_CACHE.popitem(last=False)
    return ent


def _live_view(ctx, table_id: int, table_info, cacheable, td,
               store) -> Chunk:
    vkey = (id(store), table_id) if cacheable else None
    if cacheable:
        with _LOCK:
            hit = _VIEW_CACHE.get(vkey)
            if hit is not None and hit[0] is td and \
                    len(hit[1].columns) == len(table_info.columns):
                _VIEW_CACHE.move_to_end(vkey)
                return hit[1]
    live_chunks: List[Chunk] = []
    for _region, chunk, alive in ctx.scan_table(table_id):
        ctx.check_killed()
        chunk = align_chunk_to_schema(chunk, table_info)
        if alive.all():
            live_chunks.append(chunk)
        else:
            live_chunks.append(chunk.take(np.nonzero(alive)[0]))
    if live_chunks:
        view = Chunk.concat(live_chunks) if len(live_chunks) > 1 \
            else live_chunks[0]
    else:
        view = empty_chunk([c.ftype for c in table_info.columns])
    if cacheable:
        with _LOCK:
            _VIEW_CACHE[vkey] = (td, view)
            while len(_VIEW_CACHE) > MAX_CACHED_INDEXES:
                _VIEW_CACHE.popitem(last=False)
    return view


def get_index(ctx, table_id: int, col_idx: int, table_info) -> SortedIndex:
    """→ index over the read view. Inside a transaction the index is built
    transiently over the staged view (staged rows must be visible)."""
    cacheable = getattr(ctx, "txn", None) is None
    td = ctx.snapshot.table_data(table_id) if cacheable else None
    store = getattr(ctx.snapshot, "store", None) if cacheable else None
    key = (id(store), table_id, col_idx) if cacheable else None

    with _LOCK:
        ent = _CACHE.get(key) if cacheable else None
        if ent is not None and ent.td is td and \
                len(ent.view.columns) == len(table_info.columns):
            _CACHE.move_to_end(key)
            return ent

    with _build_span(table_info, table_info.columns[col_idx].name):
        view = _live_view(ctx, table_id, table_info, cacheable, td, store)
        ctx.check_killed()
        col = view.columns[col_idx]
        vals, valid = col.values, col.valid_mask()
        n = len(vals)
        pos = np.arange(n, dtype=np.int64)
        nn_pos = pos[valid]
        order = np.argsort(vals[valid], kind="stable")
        ent = SortedIndex(td, vals[valid][order], nn_pos[order],
                          pos[~valid], n, view)
        timeline.tag(rows=n)
    if cacheable:
        with _LOCK:
            _CACHE[key] = ent
            while len(_CACHE) > MAX_CACHED_INDEXES:
                _CACHE.popitem(last=False)
    return ent


class IndexScanExec(MaterializingExec):
    """Range/point access through a sorted index (ref: point_get.go /
    IndexReader): probe → gather matching rows → residual filters."""

    def __init__(self, plan):
        super().__init__(plan.schema.field_types, [])
        self.plan = plan

    def runtime_info(self) -> str:
        return f"index:{self.plan.index_name} ranges:{self.plan.ranges!r}"

    def _materialize(self) -> Chunk:
        with timeline.span("index.probe", "index",
                           ranges=len(self.plan.ranges)):
            out = self._probe()
            timeline.tag(rows=out.num_rows)
        REGISTRY.inc("tidb_tpu_index_probes_total")
        REGISTRY.inc("tidb_tpu_index_rows_total", by=out.num_rows)
        return out

    def _probe(self) -> Chunk:
        plan = self.plan
        key_cols = getattr(plan, "key_cols", None)
        if key_cols and len(key_cols) > 1:
            ent = get_prefix_index(self.ctx, plan.table.id, key_cols,
                                   plan.table)
            rows = ent.probe(list(plan.prefix_vals), plan.ranges)
        else:
            ent = get_index(self.ctx, plan.table.id, plan.key_col,
                            plan.table)
            rows = ent.probe(plan.ranges)
        if not len(rows):
            return empty_chunk(self.schema)
        out = ent.view.take(rows)
        for pred in plan.residual:
            keep = filter_mask(pred, out)
            if not keep.all():
                out = out.take(np.nonzero(keep)[0])
        return out


class IndexOrderedScanExec(MaterializingExec):
    """Full scan emitted in index-key order — the executor behind ORDER BY
    elimination (plan: PhysIndexOrderedScan). NULLs first ascending, last
    descending (MySQL sort order); ties keep the index's stable order."""

    def __init__(self, plan):
        super().__init__(plan.schema.field_types, [])
        self.plan = plan

    def runtime_info(self) -> str:
        return (f"index_ordered:{self.plan.table.name}."
                f"{self.plan.index_name}"
                + (" desc" if self.plan.desc else ""))

    def _materialize(self) -> Chunk:
        plan = self.plan
        si = get_index(self.ctx, plan.table.id, plan.key_col, plan.table)
        if plan.desc:
            pos = np.concatenate([si.sorted_pos[::-1], si.null_pos])
        else:
            pos = np.concatenate([si.null_pos, si.sorted_pos])
        if not len(pos):
            return empty_chunk(self.schema)
        out = si.view.take(pos)
        for pred in plan.filters:
            keep = filter_mask(pred, out)
            if not keep.all():
                out = out.take(np.nonzero(keep)[0])
        return out
