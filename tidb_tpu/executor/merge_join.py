"""Sort-merge join over cached sorted-index views.

Ref: executor/merge_join.go — the reference merge-joins inputs that
arrive in key order (index readers). The columnar analog: both sides'
SortedIndex views (executor/index_scan.py) ARE the key-ordered inputs,
built once per table version and cached, so the join is two vectorized
binary searches + a prefix-sum pair expansion — no per-query hash build,
no re-sort. Chosen by the planner when both sides are indexed on their
join keys and both are too large for the index-lookup join's small-outer
gate (planner/physical.py _try_merge_join).
"""

from __future__ import annotations

from typing import List

import numpy as np

from tidb_tpu.chunk import Chunk
from tidb_tpu.executor import MaterializingExec, empty_chunk
from tidb_tpu.expression.runner import filter_mask
from tidb_tpu.executor.index_scan import get_index


class MergeJoinExec(MaterializingExec):
    """plan: PhysMergeJoin — both sides are tables with sorted indexes on
    the equi key; inner join only (outer shapes route to hash join)."""

    def __init__(self, plan):
        super().__init__(plan.schema.field_types, [])
        self.plan = plan

    def runtime_info(self) -> str:
        return (f"merge_join:{self.plan.left_table.name}."
                f"{self.plan.left_index}×{self.plan.right_table.name}."
                f"{self.plan.right_index}")

    def _materialize(self) -> Chunk:
        plan = self.plan
        li = get_index(self.ctx, plan.left_table.id, plan.left_key,
                       plan.left_table)
        ri = get_index(self.ctx, plan.right_table.id, plan.right_key,
                       plan.right_table)
        lv, lp = li.sorted_vals, li.sorted_pos
        rv, rp = ri.sorted_vals, ri.sorted_pos
        if not len(lv) or not len(rv):
            return empty_chunk(self.schema)
        lo = np.searchsorted(rv, lv, side="left")
        hi = np.searchsorted(rv, lv, side="right")
        counts = hi - lo
        total = int(counts.sum())
        if total == 0:
            return empty_chunk(self.schema)
        l_slot = np.repeat(np.arange(len(lv)), counts)
        offs = np.arange(total) - np.repeat(np.cumsum(counts) - counts,
                                            counts)
        r_slot = np.repeat(lo, counts) + offs
        left_rows = li.view.take(lp[l_slot])
        right_rows = ri.view.take(rp[r_slot])
        keep = np.ones(total, dtype=bool)
        for pred in plan.left_filters:
            keep &= filter_mask(pred, left_rows)
        for pred in plan.right_filters:
            keep &= filter_mask(pred, right_rows)
        joined = Chunk(list(left_rows.columns) + list(right_rows.columns))
        for pred in plan.other_conditions:
            keep &= filter_mask(pred, joined)
        if not keep.all():
            joined = joined.take(np.nonzero(keep)[0])
        return joined
