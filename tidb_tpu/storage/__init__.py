"""In-memory columnar region store with snapshot reads + optimistic txns.

Ref: /root/reference/store/mockstore/unistore/ — the reference embeds a full
TiKV mock (badger MVCC, Percolator 2PC, region splits) so the whole SQL stack
runs in one process. The TPU-first re-design stores data COLUMNAR from the
start (the reference stores rows and re-columnarizes in every coprocessor
scan): a table is an append-only list of immutable Regions, each one Chunk of
up to REGION_ROWS rows plus a copy-on-write deletion bitmap. Regions are the
parallel-scan unit exactly like TiKV regions are the coprocessor-task unit
(store/copr/coprocessor.go:178) — and, later, the device-shard unit.

Concurrency model (ref: optimistic txns, session/txn.go + Percolator):
  * readers take an immutable Snapshot (region list + bitmap refs) — no locks;
  * writers stage inserts/deletes in a MemBuffer (ref: txn memBuffer) and
    apply atomically at commit under the store lock;
  * conflicts: first-committer-wins on row deletes (a row deleted by two
    overlapping txns raises TxnConflict for the second — the Percolator
    write-conflict analog).
"""

from __future__ import annotations

import itertools
import time as _time
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from tidb_tpu.chunk import Chunk, Column
from tidb_tpu.errors import DeadlockError, TxnError, UnknownTableError
from tidb_tpu.util import timeline
from tidb_tpu.util.observability import REGISTRY

REGION_ROWS = 1 << 16  # region split threshold (ref: TiKV region ~96MB)


@dataclass(frozen=True)
class Region:
    """One immutable slab of rows. `deleted` is copy-on-write: never mutated
    after publication, so snapshot readers are race-free. `part` tags the
    table partition every row of this region belongs to (INSERT routes
    rows so regions never mix partitions — region-level colocation is the
    pruning unit, the slab-native analog of a partition's own region set
    in table/tables/partition.go).

    `live_rows` (rows whose `deleted` bit is clear) is a stored fact, fixed
    with the bitmap: every site of `Store` that makes a region knows the
    count and hands it over, so no reader scans `deleted` for it. Left
    out (tests, tools), the bitmap is counted once, here, and
    `tidb_tpu_live_rows_recounts_total` says that it was."""

    id: int
    chunk: Chunk
    deleted: np.ndarray  # bool (n_rows,)
    part: Optional[int] = None
    live_rows: Optional[int] = None

    def __post_init__(self):
        if self.live_rows is None:
            REGISTRY.inc("tidb_tpu_live_rows_recounts_total")
            object.__setattr__(
                self, "live_rows",
                len(self.deleted) - int(np.count_nonzero(self.deleted)))

    @property
    def num_rows(self) -> int:
        return self.chunk.num_rows


@dataclass(frozen=True)
class TableData:
    """A table's regions, with the sum of their live rows made once, when
    the tuple is (a commit rebuilds the tuple anyway): `live_rows` is a
    field read for the plan-cache key, the planner's estimate and the
    introspection tables."""

    regions: Tuple[Region, ...]
    live_rows: int = field(init=False)

    def __post_init__(self):
        object.__setattr__(self, "live_rows",
                           sum(r.live_rows for r in self.regions))


class Snapshot:
    """Immutable point-in-time view (ref: kv.Snapshot, kv/kv.go:373)."""

    def __init__(self, tables: Dict[int, TableData], version: int,
                 store: "Store" = None):
        self._tables = tables
        self.version = version
        self.store = store        # owning engine's store (device-cache key)

    def table_data(self, table_id: int) -> TableData:
        td = self._tables.get(table_id)
        if td is None:
            raise UnknownTableError(f"no storage for table id {table_id}")
        return td

    def has_table(self, table_id: int) -> bool:
        return table_id in self._tables

    def scan(self, table_id: int, parts=None
             ) -> Iterable[Tuple[Region, np.ndarray]]:
        """Yield (region, alive_mask) pairs — the coprocessor-task stream.
        `parts` (a set of partition ordinals) SKIPS non-matching regions:
        region-level partition pruning, zero bytes touched for pruned
        partitions."""
        for r in self.table_data(table_id).regions:
            if parts is not None and r.part is not None \
                    and r.part not in parts:
                continue
            yield r, ~r.deleted


class Store:
    """The storage engine singleton (ref: kv.Storage, kv/kv.go:409)."""

    # MVCC history bounds (ref: store/gcworker safepoint discipline)
    MAX_HISTORY = 256
    GC_LIFE_SECONDS = 600.0

    def __init__(self):
        self._lock = timeline.named_lock("store")
        self._tables: Dict[int, TableData] = {}
        self._region_ids = itertools.count(1)
        self._version = 0
        self._open_txns = 0     # compaction defers while txns are open
        # version history for AS OF reads: (version, wall time, tables).
        # Region objects are immutable and shared, so an entry costs one
        # dict — the MVCC version chain without per-row versions
        self._history: List[Tuple[int, float, Dict[int, TableData]]] = [
            (0, _time.time(), {})]
        # pessimistic row locks: (table_id, region_id) → {row → txn_id}
        # (ref: the TiKV lock CF the pessimistic mode acquires through)
        self._locks: Dict[Tuple[int, int], Dict[int, int]] = {}
        # wait-for edges between blocked pessimistic txns: waiter → owner
        # (the deadlock detector's graph, unistore/tikv/detector.go:24)
        self._waits: Dict[int, int] = {}
        self._txn_seq = itertools.count(1)

    def _bump_locked(self) -> None:
        self._version += 1
        now = _time.time()
        self._history.append((self._version, now, dict(self._tables)))
        cutoff = now - self.GC_LIFE_SECONDS
        while len(self._history) > self.MAX_HISTORY or (
                len(self._history) > 1 and self._history[1][1] <= cutoff
                and self._history[0][1] < cutoff):
            self._history.pop(0)

    def snapshot_at(self, ts: float) -> Snapshot:
        """Historical read view: the newest version committed at or
        before `ts` (the tidb_snapshot / AS OF TIMESTAMP read path)."""
        with self._lock:
            best = None
            for v, t, tables in self._history:
                if t <= ts:
                    best = (v, tables)
                else:
                    break
            if best is None:
                raise TxnError(
                    "snapshot is older than the GC safepoint "
                    "(tidb_gc_life_time)")
            return Snapshot(dict(best[1]), best[0], self)

    def table_history(self, table_id: int) -> set:
        """ids of every `TableData` of the table that a snapshot can still
        be handed: the current one and those of the version history. The
        device cache frees a kept generation whose data is none of them."""
        with self._lock:
            held = {id(tables.get(table_id))
                    for _v, _t, tables in self._history}
            held.add(id(self._tables.get(table_id)))
        return held

    def table_versions(self, table_id: int, after: int, upto: int) -> list:
        """[(version, TableData)] of the table at every commit that changed
        it with `after` < version <= `upto`, oldest first, as far back as
        the version history reaches: what the device cache steps through
        when a read finds it several commits behind."""
        out, last = [], None
        with self._lock:
            for v, _t, tables in self._history:
                if v > upto:
                    break
                td = tables.get(table_id)
                if td is not last and v > after and td is not None:
                    out.append((v, td))
                last = td
        return out

    # ---- pessimistic row locks -------------------------------------------
    def lock_rows(self, txn: "Transaction", table_id: int,
                  region_masks: Dict[int, np.ndarray],
                  timeout_s: float = 5.0) -> None:
        """Acquire row locks, waiting (bounded) on conflicting owners —
        SELECT ... FOR UPDATE / pessimistic-DML semantics. Lock-wait
        beyond the timeout raises the MySQL lock-wait error."""
        deadline = _time.time() + timeout_s
        while True:
            with self._lock:
                blocker = None
                for rid, mask in region_masks.items():
                    owners = self._locks.get((table_id, rid))
                    if not owners:
                        continue
                    for row in np.nonzero(mask)[0]:
                        o = owners.get(int(row))
                        if o is not None and o != txn.txn_id:
                            blocker = o
                            break
                    if blocker is not None:
                        break
                if blocker is None:
                    self._waits.pop(txn.txn_id, None)
                    for rid, mask in region_masks.items():
                        owners = self._locks.setdefault((table_id, rid), {})
                        for row in np.nonzero(mask)[0]:
                            owners[int(row)] = txn.txn_id
                        txn.locked.append((table_id, rid, mask.copy()))
                    return
                # wait-for edge + cycle walk (detector.go:Detect): if this
                # wait closes a cycle, the closing waiter aborts with
                # ER 1213 in milliseconds instead of stalling every txn
                # in the cycle to its full lock_wait_timeout
                self._waits[txn.txn_id] = blocker
                seen = set()
                cur = blocker
                while cur is not None and cur not in seen:
                    if cur == txn.txn_id:
                        self._waits.pop(txn.txn_id, None)
                        raise DeadlockError(
                            "Deadlock found when trying to get lock; "
                            "try restarting transaction")
                    seen.add(cur)
                    cur = self._waits.get(cur)
            if _time.time() >= deadline:
                with self._lock:
                    self._waits.pop(txn.txn_id, None)
                raise TxnError(
                    "Lock wait timeout exceeded; try restarting "
                    "transaction")
            _time.sleep(0.005)

    def release_entries(self, txn: "Transaction", entries) -> None:
        """Release a subset of a txn's lock entries (stale retry
        iterations of a pessimistic statement)."""
        with self._lock:
            self._release_entries_locked(txn, entries)

    def _release_entries_locked(self, txn, entries) -> None:
        for tid, rid, mask in entries:
            owners = self._locks.get((tid, rid))
            if not owners:
                continue
            for row in np.nonzero(mask)[0]:
                if owners.get(int(row)) == txn.txn_id:
                    del owners[int(row)]
            if not owners:
                del self._locks[(tid, rid)]

    def release_locks(self, txn: "Transaction") -> None:
        with self._lock:
            self._release_entries_locked(txn, txn.locked)
            txn.locked.clear()
            self._waits.pop(txn.txn_id, None)

    # ---- lifecycle -------------------------------------------------------
    def create_table(self, table_id: int) -> None:
        with self._lock:
            self._tables.setdefault(table_id, TableData(()))
            self._bump_locked()

    def drop_table(self, table_id: int) -> None:
        with self._lock:
            self._tables.pop(table_id, None)
            self._bump_locked()

    def truncate_table(self, table_id: int) -> None:
        with self._lock:
            if table_id not in self._tables:
                raise UnknownTableError(f"no storage for table id {table_id}")
            self._tables[table_id] = TableData(())
            self._bump_locked()

    # ---- reads -----------------------------------------------------------
    def snapshot(self) -> Snapshot:
        with self._lock:
            return Snapshot(dict(self._tables), self._version, self)

    @property
    def version(self) -> int:
        """Monotonic commit version — bumps on every applied write/DDL.
        The device cache stamps it on each generation (delta version)."""
        with self._lock:
            return self._version

    # ---- writes (autocommit fast path) -----------------------------------
    def append(self, table_id: int, chunk: Chunk,
               part: Optional[int] = None) -> None:
        """Append rows, splitting into REGION_ROWS regions."""
        with self._lock:
            self._append_locked(table_id, chunk, part)
            self._bump_locked()

    def _append_locked(self, table_id: int, chunk: Chunk,
                       part: Optional[int] = None) -> None:
        td = self._tables.get(table_id)
        if td is None:
            raise UnknownTableError(f"no storage for table id {table_id}")
        regions = list(td.regions)
        # top off the last region if it has headroom and is undeleted-pure
        for start in range(0, chunk.num_rows, REGION_ROWS):
            piece = chunk.slice(start, min(start + REGION_ROWS,
                                           chunk.num_rows))
            if (regions and regions[-1].num_rows + piece.num_rows
                    <= REGION_ROWS
                    and regions[-1].live_rows == regions[-1].num_rows
                    and regions[-1].part == part
                    and regions[-1].chunk.num_cols == piece.num_cols):
                # layouts must match: a region written before ADD COLUMN
                # keeps its narrow layout (padded at read); new rows with
                # the wider layout start a fresh region — and regions
                # never mix partitions
                last = regions[-1]
                merged = Chunk.concat([last.chunk, piece])
                regions[-1] = Region(last.id, merged,
                                     np.zeros(merged.num_rows, dtype=bool),
                                     part, merged.num_rows)
            else:
                regions.append(Region(next(self._region_ids), piece,
                                      np.zeros(piece.num_rows, dtype=bool),
                                      part, piece.num_rows))
        self._tables[table_id] = TableData(tuple(regions))

    GC_DEAD_RATIO = 0.5     # compact when half a table is tombstones

    def delete(self, table_id: int, region_masks: Dict[int, np.ndarray]) -> int:
        """Mark rows deleted; masks are keyed by region id. Returns count."""
        with self._lock:
            n = self._delete_locked(table_id, region_masks)
            self._maybe_compact_locked(table_id)
            self._bump_locked()
            return n

    def _maybe_compact_locked(self, table_id: int,
                              closing: int = 0) -> None:
        """GC (ref: store/gcworker/gc_worker.go — MVCC version GC; here
        tombstone reclamation): rewrite regions dropping deleted rows once
        the dead fraction crosses GC_DEAD_RATIO. Produces fresh TableData,
        so every identity-keyed cache (HBM tables, sorted indexes)
        invalidates for free."""
        if self._open_txns - closing > 0:
            # an open txn may hold staged deletes against current region
            # ids; rewriting them would abort it spuriously (GC safepoint
            # discipline, gc_worker.go — don't GC under active readers);
            # `closing` excludes the txn whose commit is applying now
            return
        td = self._tables.get(table_id)
        if td is None or not td.regions:
            return
        total = sum(r.num_rows for r in td.regions)
        dead = total - td.live_rows
        if total == 0 or dead / total < self.GC_DEAD_RATIO:
            return
        regions = []
        for r in td.regions:
            if r.live_rows == r.num_rows:
                regions.append(r)
                continue
            if r.live_rows == 0:
                continue            # fully dead region vanishes
            kept = r.chunk.take(np.nonzero(~r.deleted)[0])
            regions.append(Region(next(self._region_ids), kept,
                                  np.zeros(kept.num_rows, dtype=bool),
                                  r.part, kept.num_rows))
        self._tables[table_id] = TableData(tuple(regions))

    def drop_partition_rows(self, table_id: int, ordinal: int,
                            remap=None) -> int:
        """TRUNCATE/DROP PARTITION: remove every region tagged `ordinal`
        wholesale (no tombstones — the partition IS the region set), and
        optionally re-tag surviving regions (DROP shifts later ordinals).
        Returns rows removed."""
        with self._lock:
            td = self._tables.get(table_id)
            if td is None:
                raise UnknownTableError(f"no storage for table {table_id}")
            kept = []
            removed = 0
            for r in td.regions:
                if r.part == ordinal:
                    removed += r.live_rows
                    continue
                if remap is not None and r.part is not None:
                    new_part = remap.get(r.part, r.part)
                    if new_part != r.part:
                        r = Region(r.id, r.chunk, r.deleted, new_part,
                                   r.live_rows)
                kept.append(r)
            self._tables[table_id] = TableData(tuple(kept))
            self._bump_locked()
            return removed

    def gc_stats(self, table_id: int):
        """(live_rows, dead_rows, regions) — observability hook. Reads the
        counts the regions carry: no bitmap is scanned, and the lock is
        held for one dict lookup, so `snapshot()` never waits for it."""
        with self._lock:
            td = self._tables.get(table_id)
        if td is None:
            return (0, 0, 0)
        total = sum(r.num_rows for r in td.regions)
        return (td.live_rows, total - td.live_rows, len(td.regions))

    def _pad_mask(self, mask: np.ndarray, region: Region) -> np.ndarray:
        """A staged mask may be shorter than the region if rows were appended
        (top-off) after the txn's snapshot: regions only ever grow at the
        tail, so the mask covers an unchanged prefix — pad with False."""
        if len(mask) == region.num_rows:
            return mask
        if len(mask) > region.num_rows:
            raise TxnError("write conflict: region shrank (truncated)")
        padded = np.zeros(region.num_rows, dtype=bool)
        padded[:len(mask)] = mask
        return padded

    def _validate_deletes_locked(self, table_id: int,
                                 region_masks: Dict[int, np.ndarray]) -> None:
        """Conflict checks only — no mutation (keeps commit atomic)."""
        td = self._tables.get(table_id)
        if td is None:
            raise TxnError("write conflict: table dropped")
        by_id = {r.id: r for r in td.regions}
        for rid, mask in region_masks.items():
            r = by_id.get(rid)
            if r is None:
                raise TxnError("write conflict: region gone (truncated)")
            mask = self._pad_mask(mask, r)
            if (r.deleted & mask).any():
                raise TxnError(
                    "write conflict: row deleted by a concurrent transaction")

    def _delete_locked(self, table_id: int,
                       region_masks: Dict[int, np.ndarray]) -> int:
        td = self._tables.get(table_id)
        if td is None:
            raise UnknownTableError(f"no storage for table id {table_id}")
        deleted_count = 0
        regions = list(td.regions)
        by_id = {r.id: i for i, r in enumerate(regions)}
        for rid, mask in region_masks.items():
            idx = by_id.get(rid)
            if idx is None:
                continue
            r = regions[idx]
            mask = self._pad_mask(mask, r)
            effective = int(np.count_nonzero(mask & ~r.deleted))
            deleted_count += effective
            regions[idx] = Region(r.id, r.chunk, r.deleted | mask, r.part,
                                  r.live_rows - effective)
        self._tables[table_id] = TableData(tuple(regions))
        return deleted_count

    # ---- transactions ----------------------------------------------------
    def begin(self) -> "Transaction":
        with self._lock:
            self._open_txns += 1
        return Transaction(self, self.snapshot())

    def _txn_closed(self) -> None:
        with self._lock:
            self._open_txns = max(self._open_txns - 1, 0)

    def commit(self, txn: "Transaction") -> None:
        from tidb_tpu.util import timeline
        with timeline.span(
                "write.commit", "write",
                tables=len(set(txn.staged_inserts) | set(txn.staged_deletes))):
            self._commit(txn)

    def _commit(self, txn: "Transaction") -> None:
        from tidb_tpu.util import failpoint, timeline
        bo = None
        while True:
            try:
                failpoint.inject("store-commit")
                failpoint.inject("commit-conflict")
                # two-phase delta append: everything above is staging
                # (host-side, txn-private); the locked block below is the
                # atomic apply+version-bump. A fault HERE — the boundary —
                # either heals through the retry loop (retryable) or
                # surfaces typed with the old delta version intact; it can
                # never leave a torn delta because nothing is applied yet.
                failpoint.inject("delta-append")
                # the commit gate: the one lock every snapshot take and
                # every commit passes; a wait here has a name of its own
                self._lock.acquire(span="commit.gate")
                try:
                    # first-committer-wins: validate EVERYTHING before
                    # applying anything, so a conflict leaves no partial
                    # writes behind
                    for tid, masks in txn.staged_deletes.items():
                        self._validate_deletes_locked(tid, masks)
                    for tid in txn.staged_inserts:
                        if tid not in self._tables:
                            raise TxnError("write conflict: table dropped")
                    tombs = rows = 0
                    for tid, masks in txn.staged_deletes.items():
                        tombs += self._delete_locked(tid, masks)
                    for tid, items in txn.staged_inserts.items():
                        for ch, part in items:
                            self._append_locked(tid, ch, part)
                            rows += ch.num_rows
                    for tid in txn.staged_deletes:
                        self._maybe_compact_locked(tid, closing=1)
                    self._bump_locked()
                finally:
                    self._lock.release()
                timeline.tag(rows=rows, tombs=tombs)
                return
            except TxnError as e:
                # only errors marked retryable (transient region churn,
                # injected conflicts) re-enter; real first-committer-wins
                # conflicts propagate immediately
                if not getattr(e, "retryable", False):
                    raise
                if bo is None:
                    from tidb_tpu.util.backoff import Backoffer
                    bo = Backoffer("store-commit", base_ms=1.0,
                                   max_ms=20.0, budget_ms=250.0)
                bo.backoff(e)

    # ---- introspection ---------------------------------------------------
    def stats(self) -> Dict[int, Tuple[int, int]]:
        """table_id → (regions, live rows): two field reads a table, no
        bitmap scanned under the lock."""
        with self._lock:
            return {tid: (len(td.regions), td.live_rows)
                    for tid, td in self._tables.items()}


class Transaction:
    """Optimistic txn: staged writes + snapshot reads (ref: session/txn.go
    LazyTxn + kv memBuffer). Readers inside the txn merge staged state via
    `scan` — the UnionScanExec pattern (executor/union_scan.go)."""

    def __init__(self, store: Store, snapshot: Snapshot):
        self._store = store
        self.snapshot = snapshot
        # table_id → [(chunk, partition ordinal or None)]
        self.staged_inserts: Dict[int, List[Tuple[Chunk, Optional[int]]]] = {}
        self.staged_deletes: Dict[int, Dict[int, np.ndarray]] = {}
        self.active = True
        self.txn_id = next(store._txn_seq)
        self.pessimistic = False
        self.locked: List[Tuple[int, int, np.ndarray]] = []
        # table_id → rows this txn modified; the session flushes it into
        # the engine's auto-analyze counters at COMMIT (never on rollback)
        self.modified: Dict[int, int] = {}

    def has_staged_writes(self) -> bool:
        return bool(self.staged_inserts) or bool(self.staged_deletes)

    # ---- writes ----------------------------------------------------------
    def append(self, table_id: int, chunk: Chunk,
               part: Optional[int] = None) -> None:
        self.staged_inserts.setdefault(table_id, []).append((chunk, part))

    def delete(self, table_id: int, region_masks: Dict[int, np.ndarray]) -> int:
        staged = self.staged_deletes.setdefault(table_id, {})
        n = 0
        for rid, mask in region_masks.items():
            prev = staged.get(rid)
            if prev is None:
                staged[rid] = mask.copy()
                n += int(mask.sum())
            else:
                n += int((mask & ~prev).sum())
                staged[rid] = prev | mask
        return n

    def delete_staged(self, table_id: int, keep_mask: np.ndarray) -> None:
        """Remove rows from this txn's own staged inserts (delete-after-insert
        inside one txn)."""
        items = self.staged_inserts.get(table_id)
        if not items:
            return
        # keep_mask follows scan order (chunks in list order); filter each
        # piece separately so partition tags survive
        kept_items = []
        off = 0
        for ch, part in items:
            m = keep_mask[off:off + ch.num_rows]
            off += ch.num_rows
            k = ch.filter(m)
            if k.num_rows:
                kept_items.append((k, part))
        self.staged_inserts[table_id] = kept_items

    # ---- reads (UnionScan merge) -----------------------------------------
    def scan(self, table_id: int, parts=None
             ) -> Iterable[Tuple[Optional[Region], Chunk, np.ndarray]]:
        """Yield (region_or_None, chunk, alive_mask): committed regions with
        staged deletes applied, then staged-insert chunks (both honoring
        partition pruning via `parts`)."""
        staged_del = self.staged_deletes.get(table_id, {})
        if self.snapshot.has_table(table_id):
            for r, alive in self.snapshot.scan(table_id, parts):
                mask = alive
                sd = staged_del.get(r.id)
                if sd is not None:
                    mask = mask & ~sd
                yield r, r.chunk, mask
        elif self._store.snapshot().has_table(table_id):
            # table created AFTER this txn began (session-private CTE
            # temp materialization): read it from the current store view
            for r, alive in self._store.snapshot().scan(table_id, parts):
                yield r, r.chunk, alive
        for ch, part in self.staged_inserts.get(table_id, []):
            if ch.num_rows and (parts is None or part is None
                                or part in parts):
                yield None, ch, np.ones(ch.num_rows, dtype=bool)

    # ---- lifecycle -------------------------------------------------------
    def commit(self) -> None:
        if not self.active:
            raise TxnError("transaction is not active")
        try:
            self._store.commit(self)
        finally:
            self.active = False
            self._store.release_locks(self)
            self._store._txn_closed()

    def rollback(self) -> None:
        if self.active:
            self._store.release_locks(self)
            self._store._txn_closed()
        self.active = False
        self.staged_inserts.clear()
        self.staged_deletes.clear()
