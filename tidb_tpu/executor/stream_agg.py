"""Stream aggregation over a cached sorted-index view.

Ref: executor/aggregate.go StreamAggExec — the reference streams rows
that arrive in group-key order from an index reader and emits a group at
every key boundary. The columnar analog: the SortedIndex view
(executor/index_scan.py) IS the key-ordered input, built once per table
version; grouping is vectorized run-boundary detection on the key column
(one comparison per row — no hash table, no factorize sort), and states
still build through the same AggFunc update machinery as everywhere else.
Chosen by cost (planner/cost.py stream_agg vs hash_agg) when the group
count is a large fraction of the input."""

from __future__ import annotations

import numpy as np

from tidb_tpu.chunk import Chunk, Column
from tidb_tpu.executor import MaterializingExec, empty_chunk
from tidb_tpu.expression.aggfuncs import build_agg
from tidb_tpu.expression.runner import filter_mask, host_context
from tidb_tpu.executor.index_scan import get_index


class StreamAggExec(MaterializingExec):
    """plan: PhysStreamAgg — single ColumnRef group key over an indexed
    scan; aggs non-distinct (the planner guarantees both)."""

    def __init__(self, plan):
        super().__init__(plan.schema.field_types, [])
        self.plan = plan

    def runtime_info(self) -> str:
        return (f"stream_agg:{self.plan.table.name}."
                f"{self.plan.index_name}")

    def _materialize(self) -> Chunk:
        plan = self.plan
        si = get_index(self.ctx, plan.table.id, plan.key_col, plan.table)
        # key order with the NULL group first (its rows are contiguous)
        pos = np.concatenate([si.null_pos, si.sorted_pos])
        if len(pos) == 0:
            return empty_chunk(self.schema)
        ch = si.view.take(pos)
        if plan.filters:
            mask = np.ones(ch.num_rows, dtype=bool)
            for f in plan.filters:
                mask &= filter_mask(f, ch)
            if not mask.all():
                pos = pos[mask]
                if len(pos) == 0:
                    return empty_chunk(self.schema)
                ch = si.view.take(pos)
        kc = ch.columns[plan.key_col]
        kv, km = kc.values, kc.valid_mask()
        n = ch.num_rows
        change = np.empty(n, dtype=bool)
        change[0] = True
        if n > 1:
            eq = (kv[1:] == kv[:-1]) & km[1:] & km[:-1]
            both_null = ~km[1:] & ~km[:-1]
            change[1:] = ~(np.asarray(eq, dtype=bool) | both_null)
        gids = np.cumsum(change) - 1
        n_groups = int(gids[-1]) + 1
        reps = np.nonzero(change)[0]

        ctx = host_context(ch)
        cols = []
        for e in plan.group_exprs:
            v, m = e.eval(ctx)
            cols.append(Column(e.ftype, np.asarray(v)[reps],
                               np.asarray(m, dtype=bool)[reps]))
        for desc in plan.aggs:
            agg = build_agg(desc)
            if desc.args:
                v, m = desc.args[0].eval(ctx)
                v = np.asarray(v)
                m = np.asarray(m, dtype=bool)
            else:                       # COUNT(*)
                v = np.zeros(n, dtype=np.int64)
                m = np.ones(n, dtype=bool)
            st = agg.init(np, n_groups)
            st = agg.update(np, st, gids, n_groups, v, m)
            fv, fm = agg.final(np, st)
            cols.append(Column(agg.ftype, np.asarray(fv),
                               np.asarray(fm, dtype=bool)))
        return Chunk(cols)
