"""Volcano executors over Chunks (ref: /root/reference/executor/).

`Executor` mirrors the reference's three-method iterator interface
(executor/executor.go:259-265: Open / Next(*chunk.Chunk) / Close);
`builder.build` mirrors executorBuilder.build (executor/builder.go:144),
the single seam where engines plug in: a PhysTpuFragment node builds a
fragment executor that runs the whole subtree as one jitted device program
instead of a CPU operator pipeline.

This module is the base every operator module imports (the context, the
iterator interface, the operators that need nothing else): it imports no
other module of the package.

All CPU operators are vectorized numpy over Chunk columns — they are both
the correctness oracle for the device kernels (the reference's vec-vs-scalar
twin-test pattern, SURVEY §4 tier 1) and the small-input fallback path.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from tidb_tpu import types as T
from tidb_tpu.chunk import Chunk, Column, DEFAULT_CHUNK_SIZE
from tidb_tpu.errors import QueryKilledError
from tidb_tpu.expression import Expression
from tidb_tpu.expression.runner import eval_on_chunk, filter_mask
from tidb_tpu.types import FieldType
from tidb_tpu.util.escalation import EscalationStats
from tidb_tpu.util.memory import Tracker
from tidb_tpu.util.phases import PhaseTimer


class ExecContext:
    """Per-statement execution context (ref: sessionctx.Context subset)."""

    def __init__(self, txn=None, snapshot=None, vars: Optional[Dict] = None,
                 guard=None, unscheduled: bool = False):
        self.txn = txn              # storage.Transaction (reads merge staged)
        self.snapshot = snapshot    # storage.Snapshot (autocommit reads)
        self.vars = vars or {}
        self.killed = False
        # run on the caller's thread as it stands: no admission, no
        # placement, no batch slot (executor/scheduler.py). The compactor's
        # warm runs are (delta._warm): a compile inside a slot would hold
        # every statement up while it lasts
        self.unscheduled = unscheduled
        # per-statement ExecutionGuard (util/guard.py): kill flag +
        # deadline + root tracker, polled at every checkpoint below
        self.guard = guard
        self.runtime_stats: Dict[int, "OperatorStats"] = {}
        # per-statement quota root (ref: memory.Tracker attached to the
        # session; tidb_mem_quota_query, 0 = unlimited) — shared with the
        # guard when one is threaded in, so OOM actions and KILL cancel
        # through one tracker
        if guard is not None and guard.mem_tracker is not None:
            self.mem_tracker = guard.mem_tracker
        else:
            quota = int(self.vars.get("tidb_mem_quota_query", 0) or 0)
            self.mem_tracker = Tracker("query", quota)
        # per-statement capacity-escalation counters (util/escalation.py):
        # shared with the guard so information_schema.processlist can read
        # them back while the statement runs
        if guard is not None:
            self.escalation = guard.escalation
        else:
            self.escalation = EscalationStats()
        # per-statement device phase timings + byte/compile ledger
        # (util/phases.py), surfaced in EXPLAIN ANALYZE runtime info,
        # the statements_summary digest profile and the trace — shared
        # with the guard so every ExecContext of one statement writes
        # into the same ledger
        if guard is not None and getattr(guard, "phases", None) is not None:
            self.phases = guard.phases
        else:
            self.phases = PhaseTimer()
        self.tracer = None         # Tracer while TRACE runs (trace.go)

    @property
    def chunk_size(self) -> int:
        return int(self.vars.get("max_chunk_size", DEFAULT_CHUNK_SIZE))

    def check_killed(self, site: str = "next"):
        if self.killed:
            raise QueryKilledError("Query execution was interrupted")
        if self.guard is not None:
            self.guard.check(site)

    def scan_table(self, table_id: int, parts=None):
        """Yield (region_or_None, chunk, alive_mask) honoring txn staging.
        `parts` = pruned partition ordinals (None = all)."""
        if self.txn is not None:
            yield from self.txn.scan(table_id, parts)
        else:
            for region, alive in self.snapshot.scan(table_id, parts):
                yield region, region.chunk, alive


class OperatorStats:
    """Per-operator runtime stats for EXPLAIN ANALYZE
    (ref: util/execdetails RuntimeStatsColl)."""

    __slots__ = ("rows", "wall_ns", "opens")

    def __init__(self):
        self.rows = 0
        self.wall_ns = 0
        self.opens = 0


class Executor:
    """Ref: executor/executor.go:259-265."""

    def __init__(self, schema: List[FieldType],
                 children: Sequence["Executor"] = ()):
        self.schema = schema
        self.children = list(children)
        self.ctx: Optional[ExecContext] = None
        self.stats = OperatorStats()

    def open(self, ctx: ExecContext) -> None:
        self.ctx = ctx
        self.stats.opens += 1
        for c in self.children:
            c.open(ctx)

    def next(self) -> Optional[Chunk]:
        """One output batch, or None when drained. The timing/kill wrapper is
        `child_next` (ref: the Next wrapper executor/executor.go:268-287)."""
        raise NotImplementedError

    def child_next(self, i: int = 0) -> Optional[Chunk]:
        self.ctx.check_killed()
        child = self.children[i]
        t0 = time.perf_counter_ns()
        chunk = child.next()
        child.stats.wall_ns += time.perf_counter_ns() - t0
        if chunk is not None:
            child.stats.rows += chunk.num_rows
        return chunk

    def close(self) -> None:
        for c in self.children:
            c.close()

    def drain(self) -> Chunk:
        """Pull everything into one Chunk (blocking-operator helper)."""
        chunks = []
        while True:
            ch = self.next()
            if ch is None:
                break
            if ch.num_rows:
                chunks.append(ch)
        if not chunks:
            return empty_chunk(self.schema)
        return Chunk.concat(chunks) if len(chunks) > 1 else chunks[0]


class MaterializingExec(Executor):
    """Blocking-operator base: materialize the whole result once, then
    paginate by ctx.chunk_size (shared by window/index/sort executors)."""

    def open(self, ctx: ExecContext) -> None:
        super().open(ctx)
        self._result: Optional[Chunk] = None
        self._offset = 0

    def _materialize(self) -> Chunk:
        raise NotImplementedError

    def next(self) -> Optional[Chunk]:
        if self._result is None:
            self._result = self._materialize()
        if self._offset >= self._result.num_rows:
            return None
        size = self.ctx.chunk_size
        out = self._result.slice(
            self._offset, min(self._offset + size, self._result.num_rows))
        self._offset += out.num_rows
        return out


class MemTableExec(MaterializingExec):
    """information_schema virtual-table scan (ref: infoschema/tables.go
    memtable retrievers): rows materialize fresh per execution."""

    def __init__(self, plan):
        super().__init__(plan.schema.field_types, [])
        self.plan = plan

    def runtime_info(self) -> str:
        return f"memtable:{self.plan.mt_name}"

    def _materialize(self) -> Chunk:
        rows = self.plan.rows_fn()
        if not rows:
            return empty_chunk(self.schema)
        cols = []
        for ci, ft in enumerate(self.schema):
            raw = [ft.encode_value(r[ci]) for r in rows]
            mask = np.array([x is not None for x in raw], dtype=bool)
            if ft.is_varlen:
                vals = np.array([x if x is not None else "" for x in raw],
                                dtype=object)
            else:
                vals = np.array([x if x is not None else 0 for x in raw],
                                dtype=ft.np_dtype)
            cols.append(Column(ft, vals, None if mask.all() else mask))
        return Chunk(cols)


def empty_chunk(schema: List[FieldType]) -> Chunk:
    cols = []
    for ft in schema:
        vals = (np.empty(0, dtype=object) if ft.is_varlen
                else np.empty(0, dtype=ft.np_dtype))
        cols.append(Column(ft, vals, None))
    return Chunk(cols)


def run_to_completion(root: Executor, ctx: ExecContext) -> List[Chunk]:
    root.open(ctx)
    try:
        out = []
        while True:
            # root chunk boundary: the drain loop is itself a guard
            # checkpoint (leaf executors have no child_next above them)
            ctx.check_killed("root-next")
            ch = root.next()
            if ch is None:
                return out
            root.stats.rows += ch.num_rows
            if ch.num_rows:
                out.append(ch)
    finally:
        root.close()


# ---------------------------------------------------------------------------
# Simple executors
# ---------------------------------------------------------------------------


class DualExec(Executor):
    """SELECT without FROM: emits n_rows empty-schema rows."""

    def __init__(self, schema, n_rows: int):
        super().__init__(schema)
        self.n_rows = n_rows
        self._done = False

    def open(self, ctx):
        super().open(ctx)
        self._done = False

    def next(self):
        if self._done:
            return None
        self._done = True
        return _dual_chunk(self.n_rows)


def _dual_chunk(n: int) -> Chunk:
    # a zero-column chunk can't carry a row count; use a hidden const column
    return Chunk([Column(T.bigint(False), np.zeros(n, dtype=np.int64), None)])


class SelectionExec(Executor):
    """Ref: executor/executor.go SelectionExec + VectorizedFilter."""

    def __init__(self, conditions: List[Expression], child: Executor):
        super().__init__(child.schema, [child])
        self.conditions = conditions

    def next(self):
        while True:
            ch = self.child_next()
            if ch is None:
                return None
            mask = None
            for cond in self.conditions:
                m = filter_mask(cond, ch)
                mask = m if mask is None else (mask & m)
            out = ch.filter(mask) if mask is not None else ch
            if out.num_rows:
                return out


class ProjectionExec(Executor):
    """Ref: executor/projection.go (vectorized, single-threaded here —
    batch-level parallelism belongs to the device path)."""

    def __init__(self, exprs: List[Expression], schema, child: Executor):
        super().__init__(schema, [child])
        self.exprs = exprs

    def next(self):
        ch = self.child_next()
        if ch is None:
            return None
        return eval_on_chunk(self.exprs, ch)


class LimitExec(Executor):
    def __init__(self, offset: int, count: int, child: Executor):
        super().__init__(child.schema, [child])
        self.offset = offset
        self.count = count
        self._skipped = 0
        self._emitted = 0

    def open(self, ctx):
        super().open(ctx)
        self._skipped = 0
        self._emitted = 0

    def next(self):
        while self._emitted < self.count:
            ch = self.child_next()
            if ch is None:
                return None
            if self._skipped < self.offset:
                drop = min(self.offset - self._skipped, ch.num_rows)
                self._skipped += drop
                ch = ch.slice(drop, ch.num_rows)
            if ch.num_rows == 0:
                continue
            take = min(self.count - self._emitted, ch.num_rows)
            self._emitted += take
            return ch.slice(0, take)
        return None


class UnionAllExec(Executor):
    def __init__(self, schema, children):
        super().__init__(schema, children)
        self._cur = 0

    def open(self, ctx):
        super().open(ctx)
        self._cur = 0

    def next(self):
        while self._cur < len(self.children):
            ch = self.child_next(self._cur)
            if ch is not None:
                return self._coerce(ch)
            self._cur += 1
        return None

    def _coerce(self, ch: Chunk) -> Chunk:
        cols = []
        for col, ft in zip(ch.columns, self.schema):
            if not ft.is_varlen and col.values.dtype != ft.np_dtype:
                cols.append(Column(ft, col.values.astype(ft.np_dtype),
                                   col.validity))
            else:
                cols.append(Column(ft, col.values, col.validity))
        return Chunk(cols)
