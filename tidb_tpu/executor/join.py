"""Hash join as dictionary-map + sorted-probe expansion (ref: executor/join.go).

The reference builds a rowptr hash table over the build side then runs N
probe workers (hashRowContainer, executor/hash_table.go). The TPU-first
reformulation avoids pointer-chasing hash tables (SURVEY A.5): build keys
are factorized into a per-column sorted dictionary; probe keys map into the
same code space by binary search (misses → no match); matches expand via
searchsorted ranges over the sorted build codes — the sort/gather pattern
that also runs well on device. If the multi-key code space overflows int64,
candidate pairs are re-verified against the real key values — the
reference's candidate-then-verify discipline (hash_table.go:110-146).

Join kinds: inner, left, right, semi, anti. NULL join keys never match
(SQL `=` semantics); the joiner-variant padding logic mirrors
executor/joiner.go:60.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from tidb_tpu.chunk import Chunk, Column
from tidb_tpu.executor import Executor, empty_chunk
from tidb_tpu import expression
from tidb_tpu.expression import Expression
from tidb_tpu.expression.runner import filter_mask, host_context
from tidb_tpu.planner.physical import PhysHashJoin
from tidb_tpu.types import fold_ci_array
from tidb_tpu.util import memory as M
from tidb_tpu.util.memory import hash_partition

_CODE_GUARD = 1 << 61


def _empty_like(ftypes) -> Chunk:
    return empty_chunk(list(ftypes))


def _key_arrays(exprs: List[Expression], chunk: Chunk,
                ci_flags: List[bool] = None):
    ctx = host_context(chunk)
    out = []
    for i, e in enumerate(exprs):
        v, m = e.eval(ctx)
        v = np.asarray(v)
        if ci_flags is not None and ci_flags[i] and v.dtype == object:
            v = fold_ci_array(v)
        out.append((v, np.asarray(m, dtype=bool)))
    return out


def equi_ci_flags(equi) -> List[bool]:
    """Per equi pair: compare under ci when EITHER side's collation is
    ci (the stronger collation wins, util/collate coercion)."""
    return [l.ftype.is_ci or r.ftype.is_ci for l, r in equi]


def _normalize(vals: np.ndarray) -> np.ndarray:
    if vals.dtype == object:
        return np.asarray([str(v) for v in vals], dtype=object)
    return vals


class _BuildTable:
    """Sorted-code join index over the build side."""

    def __init__(self, build_keys):
        n = len(build_keys[0][0]) if build_keys else 0
        self.n_rows = n
        combined = np.zeros(n, dtype=np.int64)
        valid_all = np.ones(n, dtype=bool)
        self.dicts = []
        self.build_vals = []
        self.needs_verify = False
        base = 1
        for vals, valid in build_keys:
            vals = _normalize(vals)
            self.build_vals.append(vals)
            uniq = np.unique(vals[valid]) if valid.any() else vals[:0]
            codes = np.searchsorted(uniq, vals) if len(uniq) else \
                np.zeros(n, dtype=np.int64)
            in_dict = codes < len(uniq)
            if len(uniq):
                in_dict &= np.asarray(
                    uniq[np.clip(codes, 0, len(uniq) - 1)] == vals)
            valid_all &= valid & in_dict
            k = len(uniq) + 1
            if base * k > _CODE_GUARD:
                self.needs_verify = True  # wraparound collisions re-checked
            with np.errstate(over="ignore"):
                combined = combined * np.int64(k) + \
                    np.where(valid_all, codes, 0)
            base = min(base * k, _CODE_GUARD + 1)
            self.dicts.append(uniq)
        self.valid = valid_all
        self.codes = np.where(valid_all, combined, np.int64(-1))
        self.order = np.argsort(self.codes, kind="stable")
        self.sorted_codes = self.codes[self.order]

    def probe(self, probe_keys) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """→ (probe_rows, build_rows, counts_per_probe_row)."""
        n = len(probe_keys[0][0]) if probe_keys else 0
        combined = np.zeros(n, dtype=np.int64)
        valid_all = np.ones(n, dtype=bool)
        pvals_list = []
        for (vals, valid), uniq in zip(probe_keys, self.dicts):
            vals = _normalize(vals)
            pvals_list.append(vals)
            codes = np.searchsorted(uniq, vals) if len(uniq) else \
                np.zeros(n, dtype=np.int64)
            hit = codes < len(uniq)
            if len(uniq):
                hit &= np.asarray(
                    uniq[np.clip(codes, 0, len(uniq) - 1)] == vals)
            valid_all &= valid & hit
            k = len(uniq) + 1
            with np.errstate(over="ignore"):
                combined = combined * np.int64(k) + \
                    np.where(valid_all, codes, 0)
        pcodes = np.where(valid_all, combined, np.int64(-2))
        left = np.searchsorted(self.sorted_codes, pcodes, side="left")
        right = np.searchsorted(self.sorted_codes, pcodes, side="right")
        counts = (right - left) * valid_all
        total = int(counts.sum())
        if total == 0:
            empty = np.empty(0, dtype=np.int64)
            return empty, empty, counts
        starts = np.repeat(left, counts)
        offs = np.arange(total, dtype=np.int64) - np.repeat(
            np.cumsum(counts) - counts, counts)
        build_rows = self.order[starts + offs]
        probe_rows = np.repeat(np.arange(n, dtype=np.int64), counts)
        if self.needs_verify:
            ok = self.valid[build_rows]  # wraparound can land on NULL-key rows
            for pv, bv in zip(pvals_list, self.build_vals):
                ok &= np.asarray(pv[probe_rows] == bv[build_rows])
            probe_rows, build_rows = probe_rows[ok], build_rows[ok]
            counts = np.bincount(probe_rows, minlength=n).astype(np.int64)
        return probe_rows, build_rows, counts


class HashJoinExec(Executor):
    N_SPILL_PARTITIONS = 16

    def __init__(self, plan: PhysHashJoin, left: Executor, right: Executor):
        super().__init__(plan.schema.field_types, [left, right])
        self.plan = plan
        self.kind = plan.kind
        self.build_right = plan.build_right
        self.equi = [expression.coerce_key_pair(l, r)
                     for l, r in plan.equi]
        self._table: Optional[_BuildTable] = None
        self._build_chunk: Optional[Chunk] = None
        self._grace = None            # (build_spill, probe_spill) if spilled
        self._grace_iter = None
        self._tracker = None
        self._tracked = 0

    def open(self, ctx):
        super().open(ctx)
        self._table = None
        self._build_chunk = None
        self._grace = None
        self._grace_iter = None
        self._tracker = None
        self._tracked = 0

    def close(self):
        super().close()
        if self._grace is not None:
            for sp in self._grace:
                sp.close()
            self._grace = None
        if self._tracker is not None and self._tracked:
            self._tracker.release(self._tracked)
            self._tracked = 0

    # ---- sides -------------------------------------------------------------
    @property
    def _build_idx(self) -> int:
        return 1 if self.build_right else 0

    @property
    def _probe_idx(self) -> int:
        return 0 if self.build_right else 1

    def _keys(self):
        left_keys = [l for l, _ in self.equi]
        right_keys = [r for _, r in self.equi]
        if self.build_right:
            return right_keys, left_keys   # (build keys, probe keys)
        return left_keys, right_keys

    def _ensure_built(self):
        if self._table is not None or self._grace is not None:
            return
        build_exec = self.children[self._build_idx]
        build_fts = build_exec.schema
        self._tracker = self.ctx.mem_tracker.child("HashJoin")
        chunks: List[Chunk] = []
        state = {"spill": None}

        def engage() -> bool:
            # grace hash join (the hashRowContainer spill,
            # executor/hash_table.go:77): partition the build side to disk
            if not self.equi or state["spill"] is not None:
                return False       # cross join cannot partition
            state["spill"] = M.PartitionedChunkSpill(
                self.N_SPILL_PARTITIONS, build_fts,
                guard=getattr(self.ctx, "guard", None))
            for ch in chunks:
                self._spill_side(state["spill"], ch, build=True)
            chunks.clear()
            self._tracker.release(self._tracked)
            self._tracked = 0
            return True

        self._tracker.add_handler(engage)
        try:
            while True:
                ch = self.child_next(self._build_idx)
                if ch is None:
                    break
                if ch.num_rows == 0:
                    continue
                if state["spill"] is not None:
                    self._spill_side(state["spill"], ch, build=True)
                    continue
                chunks.append(ch)
                b = M.chunk_bytes(ch)
                self._tracked += b
                self._tracker.consume(b)
        finally:
            self._tracker.remove_handler(engage)
        if state["spill"] is not None:
            probe_fts = self.children[self._probe_idx].schema
            self._grace = (state["spill"],
                           M.PartitionedChunkSpill(
                               self.N_SPILL_PARTITIONS, probe_fts,
                               guard=getattr(self.ctx, "guard", None)))
            return
        self._build_chunk = (Chunk.concat(chunks) if len(chunks) > 1
                             else chunks[0] if chunks
                             else _empty_like(build_fts))
        build_key_exprs, _ = self._keys()
        bkeys = _key_arrays(build_key_exprs, self._build_chunk,
                            equi_ci_flags(self.equi))
        self._table = _BuildTable(bkeys)

    def _spill_side(self, spill, chunk: Chunk, build: bool) -> None:
        build_key_exprs, probe_key_exprs = self._keys()
        exprs = build_key_exprs if build else probe_key_exprs
        keys = _key_arrays(exprs, chunk, equi_ci_flags(self.equi))
        keys = [(_normalize(v), m) for v, m in keys]
        spill.add_partitioned(chunk, hash_partition(keys, spill.n))

    def _grace_results(self):
        """Partition-at-a-time join: per partition, an in-memory build over
        ~1/P of the build side, probing that partition's probe chunks.
        A skewed partition that alone exceeds the quota cancels honestly
        (tracked consume raises) instead of silently re-inflating."""
        build_spill, probe_spill = self._grace
        build_key_exprs, _ = self._keys()
        for p in range(build_spill.n):
            self.ctx.check_killed()
            if self._tracked:
                self._tracker.release(self._tracked)
                self._tracked = 0
            bchunks = list(build_spill.read(p))
            part_bytes = sum(M.chunk_bytes(c) for c in bchunks)
            self._tracked = part_bytes
            self._tracker.consume(part_bytes)
            self._build_chunk = (Chunk.concat(bchunks)
                                 if len(bchunks) > 1 else bchunks[0]
                                 if bchunks else
                                 _empty_like(self.children[
                                     self._build_idx].schema))
            self._table = _BuildTable(
                _key_arrays(build_key_exprs, self._build_chunk,
                            equi_ci_flags(self.equi)))
            for probe in probe_spill.read(p):
                out = self._join_chunk(probe)
                if out is not None and out.num_rows:
                    yield out

    # ---- volcano -----------------------------------------------------------
    def next(self) -> Optional[Chunk]:
        self._ensure_built()
        if self._grace is not None:
            if self._grace_iter is None:
                # drain + partition the probe side, then join per partition
                while True:
                    probe = self.child_next(self._probe_idx)
                    if probe is None:
                        break
                    if probe.num_rows:
                        self._spill_side(self._grace[1], probe,
                                         build=False)
                self._grace_iter = self._grace_results()
            return next(self._grace_iter, None)
        while True:
            probe = self.child_next(self._probe_idx)
            if probe is None:
                return None
            out = self._join_chunk(probe)
            if out is not None and out.num_rows:
                return out

    # ---- joining one probe chunk --------------------------------------------
    def _match(self, probe: Chunk):
        if self.equi:
            _, probe_key_exprs = self._keys()
            pkeys = _key_arrays(probe_key_exprs, probe,
                                equi_ci_flags(self.equi))
            return self._table.probe(pkeys)
        # no equi keys: full cross expansion, conditions filter later
        nb = self._build_chunk.num_rows
        npr = probe.num_rows
        probe_rows = np.repeat(np.arange(npr, dtype=np.int64), nb)
        build_rows = np.tile(np.arange(nb, dtype=np.int64), npr)
        counts = np.full(npr, nb, dtype=np.int64)
        return probe_rows, build_rows, counts

    def _join_chunk(self, probe: Chunk) -> Optional[Chunk]:
        probe_rows, build_rows, counts = self._match(probe)

        if self.kind in ("semi", "anti"):
            return self._semi_anti(probe, probe_rows, build_rows, counts)

        pairs = self._pairs_chunk(probe, probe_rows, build_rows)
        if self.plan.other_conditions and pairs.num_rows:
            mask = self._other_mask(pairs)
            pairs = pairs.filter(mask)
            surviving = np.bincount(probe_rows[mask],
                                    minlength=probe.num_rows)
        else:
            surviving = counts

        if self.kind == "inner":
            return pairs
        unmatched = np.nonzero(surviving == 0)[0]
        if len(unmatched) == 0:
            return pairs
        padded = self._padded_chunk(probe, unmatched)
        return Chunk.concat([pairs, padded]) if pairs.num_rows else padded

    def _semi_anti(self, probe, probe_rows, build_rows, counts):
        if self.plan.other_conditions and len(probe_rows):
            pairs = self._pairs_chunk(probe, probe_rows, build_rows)
            mask = self._other_mask(pairs)
            surviving = np.bincount(probe_rows[mask],
                                    minlength=probe.num_rows)
        else:
            surviving = counts
        keep = (surviving > 0) if self.kind == "semi" else (surviving == 0)
        return probe.filter(keep)

    # ---- chunk assembly -----------------------------------------------------
    def _pairs_chunk(self, probe: Chunk, probe_rows, build_rows) -> Chunk:
        ptaken = probe.take(probe_rows)
        btaken = self._build_chunk.take(build_rows)
        if self.build_right:
            cols = list(ptaken.columns) + list(btaken.columns)
        else:
            cols = list(btaken.columns) + list(ptaken.columns)
        if self.kind in ("semi", "anti"):
            return Chunk(cols)  # schema stamping happens on probe emit
        return self._retype(Chunk(cols))

    def _padded_chunk(self, probe: Chunk, unmatched) -> Chunk:
        ptaken = probe.take(unmatched)
        n = len(unmatched)
        build_schema = [c.ftype for c in self._build_chunk.columns]
        nulls = [Column.all_null(ft, n) for ft in build_schema]
        if self.build_right:
            cols = list(ptaken.columns) + nulls
        else:
            cols = nulls + list(ptaken.columns)
        return self._retype(Chunk(cols))

    def _retype(self, ch: Chunk) -> Chunk:
        """Stamp output nullability (outer joins null-extend the inner side)."""
        cols = [Column(ft, c.values, c.validity)
                for ft, c in zip(self.schema, ch.columns)]
        return Chunk(cols)

    def _other_mask(self, pairs: Chunk) -> np.ndarray:
        mask = None
        for cond in self.plan.other_conditions:
            m = filter_mask(cond, pairs)
            mask = m if mask is None else (mask & m)
        return mask if mask is not None else np.ones(pairs.num_rows,
                                                     dtype=bool)
