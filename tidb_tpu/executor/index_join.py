"""Index-lookup (index nested-loop) join.

Ref: executor/index_lookup_join.go:59 — the reference batches outer rows,
builds index key ranges from them, and reads matching inner rows through
the index instead of scanning the inner table. The columnar analog probes
the SortedIndex view (executor/index_scan.py) with ALL outer keys at once:
one np.searchsorted pair over the sorted key column yields every match
window, prefix-sums expand the pairs, and the inner table is touched only
at the matched positions — O(outer·log inner + matches), no inner scan.

Chosen by the planner for small-outer/large-indexed-inner equi joins
(planner/physical.py _try_index_join); supports inner/left/semi/anti with
the probe (outer) side preserved.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from tidb_tpu.chunk import Chunk, Column
from tidb_tpu.executor import MaterializingExec, empty_chunk
from tidb_tpu.expression.runner import eval_on_chunk, filter_mask
from tidb_tpu.executor.index_scan import get_index


class IndexLookupJoinExec(MaterializingExec):
    """plan: PhysIndexLookupJoin — children[0] is the outer (probe) side;
    the inner side is a table + indexed key column, never scanned."""

    def __init__(self, plan, outer_exec):
        super().__init__(plan.schema.field_types, [outer_exec])
        self.plan = plan

    def runtime_info(self) -> str:
        return (f"index_join:{self.plan.inner_table.name}."
                f"{self.plan.index_name}")

    def _materialize(self) -> Chunk:
        plan = self.plan
        outer_chunks: List[Chunk] = []
        while True:
            ch = self.child_next(0)      # kill-check + child stats
            if ch is None:
                break
            if ch.num_rows:
                outer_chunks.append(ch)
        if not outer_chunks:
            return empty_chunk(self.schema)
        outer = Chunk.concat(outer_chunks) if len(outer_chunks) > 1 \
            else outer_chunks[0]

        ent = get_index(self.ctx, plan.inner_table.id, plan.inner_key_col,
                        plan.inner_table)
        kcol = eval_on_chunk([plan.outer_key], outer).columns[0]
        keys = kcol.values
        kvalid = kcol.valid_mask()
        if keys.dtype == object:
            keys = np.asarray([str(x) for x in keys], dtype=object)

        sv = ent.sorted_vals
        n_out = outer.num_rows
        if len(sv):
            lo = np.searchsorted(sv, keys, side="left")
            hi = np.searchsorted(sv, keys, side="right")
        else:
            lo = np.zeros(n_out, dtype=np.int64)
            hi = lo
        counts = np.where(kvalid, hi - lo, 0)

        # expand (outer row, inner position) match pairs via prefix sums
        total = int(counts.sum())
        if total:
            o_idx = np.repeat(np.arange(n_out), counts)
            starts = np.repeat(lo, counts)
            offs = np.arange(total) - np.repeat(
                np.cumsum(counts) - counts, counts)
            i_pos = ent.sorted_pos[starts + offs]
        else:
            o_idx = np.empty(0, dtype=np.int64)
            i_pos = np.empty(0, dtype=np.int64)

        inner_rows = ent.view.take(i_pos)
        # inner-side pushed-down filters run on the matched rows only
        keep = np.ones(len(i_pos), dtype=bool)
        for pred in plan.inner_filters:
            keep &= filter_mask(pred, inner_rows)
        if plan.other_conditions:
            joined = Chunk(list(outer.take(o_idx).columns)
                           + list(inner_rows.columns))
            for pred in plan.other_conditions:
                keep &= filter_mask(pred, joined)
        if not keep.all():
            o_idx = o_idx[keep]
            i_pos = i_pos[keep]
            inner_rows = inner_rows.take(np.nonzero(keep)[0])

        kind = plan.kind
        if kind in ("semi", "anti"):
            matched = np.zeros(n_out, dtype=bool)
            matched[o_idx] = True
            pick = matched if kind == "semi" else ~matched
            return outer.take(np.nonzero(pick)[0])
        if kind == "inner":
            return Chunk(list(outer.take(o_idx).columns)
                         + list(inner_rows.columns))
        # left outer: unmatched outer rows null-extend the inner side
        matched = np.zeros(n_out, dtype=bool)
        matched[o_idx] = True
        miss = np.nonzero(~matched)[0]
        all_o = np.concatenate([o_idx, miss])
        order = np.argsort(all_o, kind="stable")
        out_cols = list(outer.take(all_o[order]).columns)
        n_miss = len(miss)
        for ci, col in enumerate(inner_rows.columns):
            ft = col.ftype.with_nullable(True)
            vals = np.concatenate(
                [col.values,
                 np.zeros(n_miss, dtype=col.values.dtype)
                 if col.values.dtype != object
                 else np.full(n_miss, None, dtype=object)])
            mask = np.concatenate([col.valid_mask(),
                                   np.zeros(n_miss, dtype=bool)])
            out_cols.append(Column(ft, vals[order], mask[order]))
        return Chunk(out_cols)
