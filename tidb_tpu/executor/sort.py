"""Sort / TopN executors (ref: executor/sort.go).

Keys are rank-encoded per column (sorted-unique codes) so one integer
lexsort handles every type, every direction, and MySQL NULL ordering
(NULLs first ASC, last DESC) uniformly — and the same rank encoding is
what the device TopN kernel consumes.

When an ORDER BY / TopN root sits directly over an aggregate, the
fused finalize (`executor/device_emit.py` ``emit_sort`` /
``emit_topk``) runs the ordering inside the same traced program as the
agg merge+finalize, and these host executors never see the rows.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from tidb_tpu.chunk import Chunk
from tidb_tpu.executor import Executor, MaterializingExec, empty_chunk
from tidb_tpu.expression import Expression
from tidb_tpu.expression.runner import host_context
from tidb_tpu.types import fold_ci_array


def rank_keys(by: List[Expression], descs: List[bool],
              chunk: Chunk) -> List[np.ndarray]:
    """Per sort key → int64 rank codes honoring direction + NULL order.
    Numbers (wide DECIMALs too) rank by value, strings by their text."""
    ctx = host_context(chunk)
    keys = []
    for e, desc in zip(by, descs):
        v, m = e.eval(ctx)
        v = np.asarray(v)
        m = np.asarray(m, dtype=bool)
        if v.dtype == object and e.ftype.is_varlen:
            v = np.asarray([str(x) for x in v], dtype=object)
            if e.ftype.is_ci:
                v = fold_ci_array(v)
        elif v.dtype == object:
            # a wide DECIMAL: scaled Python ints, ranked by VALUE (their text
            # would put 9976 above 495455). int64 where they fit, for speed.
            try:
                v = v.astype(np.int64)
            except OverflowError:
                pass
        uniq = np.unique(v[m]) if m.any() else v[:0]
        codes = (np.searchsorted(uniq, v) if len(uniq)
                 else np.zeros(len(v), dtype=np.int64)).astype(np.int64) + 1
        codes = np.where(m, codes, 0)          # NULL → 0 (first, ASC)
        if desc:
            codes = (len(uniq) + 1) - codes    # NULL → max (last, DESC)
        keys.append(codes)
    return keys


def sort_indices(by, descs, chunk: Chunk) -> np.ndarray:
    keys = rank_keys(by, descs, chunk)
    # np.lexsort: last key is primary → reverse; stable within equal keys
    return np.lexsort(tuple(reversed(keys)))


class SortExec(MaterializingExec):
    def __init__(self, by: List[Expression], descs: List[bool],
                 child: Executor):
        super().__init__(child.schema, [child])
        self.by = by
        self.descs = descs

    def _materialize(self) -> Chunk:
        data = self.children[0].drain()
        if not data.num_rows:
            return data
        return data.take(sort_indices(self.by, self.descs, data))


class TopNExec(MaterializingExec):
    """Heap-free TopN: keep a bounded candidate set per batch — argpartition
    against the (offset+count) bound, full sort only at the end
    (ref: executor/sort.go TopNExec's heap, reformulated batch-wise)."""

    def __init__(self, by, descs, offset: int, count: int, child: Executor):
        super().__init__(child.schema, [child])
        self.by = by
        self.descs = descs
        self.offset = offset
        self.count = count

    def _materialize(self) -> Chunk:
        bound = self.offset + self.count
        candidate: Optional[Chunk] = None
        while True:
            ch = self.child_next()
            if ch is None:
                break
            if ch.num_rows == 0:
                continue
            merged = ch if candidate is None else Chunk.concat(
                [candidate, ch])
            if merged.num_rows > bound * 2:
                # prune: keep the best `bound` rows (ordering finalized later)
                idx = sort_indices(self.by, self.descs, merged)[:bound]
                candidate = merged.take(np.sort(idx))
            else:
                candidate = merged
        if candidate is None or candidate.num_rows == 0:
            return empty_chunk(self.schema)
        idx = sort_indices(self.by, self.descs, candidate)
        idx = idx[self.offset:bound]
        return candidate.take(idx)
