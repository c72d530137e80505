"""Physical plan → executor tree (ref: executor/builder.go:144 — the
engine seam): the one module that knows every operator. A PhysTpuFragment
builds the device executor, which is handed `build` itself for the CPU
subtree it falls back to.
"""

from __future__ import annotations

from tidb_tpu.errors import ExecutionError
from tidb_tpu.executor import (DualExec, Executor, LimitExec, MemTableExec,
                               ProjectionExec, SelectionExec, UnionAllExec)
from tidb_tpu.executor.fragment import TpuFragmentExec
from tidb_tpu.executor.hash_agg import HashAggExec
from tidb_tpu.executor.index_join import IndexLookupJoinExec
from tidb_tpu.executor.index_scan import IndexOrderedScanExec, IndexScanExec
from tidb_tpu.executor.join import HashJoinExec
from tidb_tpu.executor.merge_join import MergeJoinExec
from tidb_tpu.executor.scan import TableScanExec
from tidb_tpu.executor.sort import SortExec, TopNExec
from tidb_tpu.executor.stream_agg import StreamAggExec
from tidb_tpu.executor.window import WindowExec
from tidb_tpu.planner.physical import (PhysDual, PhysHashAgg, PhysHashJoin,
                                       PhysIndexLookupJoin,
                                       PhysIndexOrderedScan, PhysIndexScan,
                                       PhysLimit, PhysMemTable,
                                       PhysMergeJoin, PhysProjection,
                                       PhysSelection, PhysSort,
                                       PhysStreamAgg, PhysTableScan,
                                       PhysTopN, PhysTpuFragment,
                                       PhysUnionAll, PhysWindow,
                                       PhysicalPlan)


def build(plan: PhysicalPlan) -> Executor:
    if isinstance(plan, PhysTpuFragment):
        return TpuFragmentExec(plan, build)
    if isinstance(plan, PhysTableScan):
        return TableScanExec(plan)
    if isinstance(plan, PhysIndexScan):
        return IndexScanExec(plan)
    if isinstance(plan, PhysMemTable):
        return MemTableExec(plan)
    if isinstance(plan, PhysMergeJoin):
        return MergeJoinExec(plan)
    if isinstance(plan, PhysStreamAgg):
        return StreamAggExec(plan)
    if isinstance(plan, PhysIndexOrderedScan):
        return IndexOrderedScanExec(plan)
    if isinstance(plan, PhysIndexLookupJoin):
        return IndexLookupJoinExec(plan, build(plan.children[0]))
    if isinstance(plan, PhysDual):
        return DualExec(plan.schema.field_types, plan.n_rows)
    kids = [build(c) for c in plan.children]
    if isinstance(plan, PhysSelection):
        return SelectionExec(plan.conditions, kids[0])
    if isinstance(plan, PhysProjection):
        return ProjectionExec(plan.exprs, plan.schema.field_types, kids[0])
    if isinstance(plan, PhysHashAgg):
        return HashAggExec(plan, kids[0])
    if isinstance(plan, PhysHashJoin):
        return HashJoinExec(plan, kids[0], kids[1])
    if isinstance(plan, PhysWindow):
        return WindowExec(plan, kids[0])
    if isinstance(plan, PhysSort):
        return SortExec(plan.by, plan.descs, kids[0])
    if isinstance(plan, PhysTopN):
        return TopNExec(plan.by, plan.descs, plan.offset, plan.count, kids[0])
    if isinstance(plan, PhysLimit):
        return LimitExec(plan.offset, plan.count, kids[0])
    if isinstance(plan, PhysUnionAll):
        return UnionAllExec(plan.schema.field_types, kids)
    raise ExecutionError(f"no executor for {type(plan).__name__}")
