"""Delta generations — the HTAP write path of the device cache.

A committed write must not cost a reader the table: TiFlash's DeltaTree
keeps a small delta layer over immutable stable packs and merges the two
at read. Here a cached table (executor/device_cache.CachedTable) whose
`TableData` went stale is diffed region by region against the current
snapshot (regions are immutable objects that only grow at the tail and
only ever lose rows, so the diff is exact) and EXTENDS into a new
generation that shares every base device array with its predecessor:

  * appended rows go into ONE delta slab at index `base_slabs`, RAW (no
    compressed layout) and of a capacity of its own (`delta_capacity`).
    Nothing a base layout assumes — a packed range, the order a `delta`
    layout codes differences in, a numeric dictionary — can be broken by
    a value that arrives later, so no value gate is left but one: a string
    outside the column's global dictionary, whose codes the compiled
    programs and the decoded results share. A write uploads the new rows
    alone (a scatter into the resident slab), never the slab;
  * a deleted row clears one bit of its slab's liveness mask
    (`CachedTable.alive`). No resident row ever moves, so base positions,
    zone maps, bounds and the FK-aligned join columns stay valid, and a
    tombstone costs the same whatever layouts the table's columns have.
    The slab programs take the mask where they took a row count
    (fragment._eval_chain, tree_fragment.TreeProgram._emit), the same
    programs under the same names.

Nothing of a generation is part of a program: no table data, no version.
What an extension cannot express declines into the rebuild that is always
correct, and every decline is counted by the gate that tripped
(`tidb_tpu_delta_declines_total{gate=}`):

  no-coverage       the entry was not built from a coverage ledger
  schema            a resident column is no longer in the scan's schema
  region-rescoped   an old region entered the scan's partition scope
  region-shrank / row-resurrected / regions-rewritten
                    the store rewrote regions (GC, TRUNCATE): positions
                    the ledger holds are gone
  dictionary        an appended string is outside the global dictionary
  delta-full        the delta slab has no room (compaction did not keep up)
  consumer          the statement runs on a path whose programs assume a
                    live prefix and uniform slabs (order/filter roots,
                    windows, the mega-slab loop, sorted-runs grouping): it
                    gets a plain rebuild cached BESIDE the generation
  behind            the statement's snapshot is OLDER than every generation
                    the cache keeps of the table (device_cache: a key's
                    newest and `KEPT_GENERATIONS` behind it): a plain
                    rebuild cached BESIDE them in a slot of its own, never
                    in the newest's place nor in the `consumer` gate's
  aligned-<why>     an FK-aligned join structure could not follow its
                    tables' generations and was rebuilt (device_cache.
                    _advance_aligned names the reasons)
  error             anything unexpected inside the extension

Compaction is the write-side half: when the delta slab is `COMPACT_FILL`
full or `COMPACT_DEAD` of the base's rows are dead — both measured on the
entry — a job rebuilds the base from a snapshot with re-chosen layouts and
fresh zone maps, in the scheduler's batch class, never in a statement. The
table may move on meanwhile: the rebuilt generation carries its snapshot's
ledger, so it extends like any other stale entry. Before the swap the
compactor runs the fragments that lately read the table once over the
rebuilt generation, extended to the newest snapshot (`_warm`): what a
re-chosen layout compiles, and the aligned structures over the new row
positions, cost its thread and not the first statement after the swap.

Failpoints: `delta-merge-stale` (entry of extend_entry — a typed
LayoutError and the executor's warned CPU fallback, never silent wrong
rows) and `compaction-commit` (between a finished rebuild and its swap: the
rebuilt buffers are deleted, the old generation keeps serving); the write
side's `delta-append` lives in storage Store.commit.
"""

from __future__ import annotations

import contextlib
import threading
import weakref
from typing import Dict, Optional

import numpy as np

from tidb_tpu.chunk import compress
from tidb_tpu.errors import LayoutError
from tidb_tpu.executor import (ExecContext, device_cache as dc, device_emit,
                               scheduler)
from tidb_tpu.executor.scan import align_chunk_to_schema
from tidb_tpu.ops.jax_env import jax
from tidb_tpu.sysvars import var_on
from tidb_tpu.types import fold_ci_array
from tidb_tpu.util import failpoint, timeline
from tidb_tpu.util.escalation import pow2
from tidb_tpu.util.guard import ExecutionGuard
from tidb_tpu.util.observability import REGISTRY
from tidb_tpu.util.phases import PhaseTimer

#: the delta slab holds slab_cap // DELTA_CAP_SHARE rows, never fewer
#: than MIN_DELTA_CAP (both powers of two, as every slab capacity is)
DELTA_CAP_SHARE = 8
MIN_DELTA_CAP = 1024
#: compaction is due when the delta slab is this full, or this share of
#: the base's rows is dead
COMPACT_FILL = 0.5
COMPACT_DEAD = 0.125
#: appended rows upload in power-of-two buckets of at least this many
MIN_BUCKET = 1024
#: steps a generation remembers for the aligned joins to catch up by
MAX_STEPS = 64

# one extension at a time: extensions are short (a region diff, one
# chunk encode and a few mask updates), and serializing them removes the
# same-entry race where two threads build sibling generations
_EXT_LOCK = timeline.named_lock("delta_extend")


def delta_capacity(slab_cap: int) -> int:
    return max(MIN_DELTA_CAP, int(slab_cap) // DELTA_CAP_SHARE)


def decline(gate: str, table_id) -> None:
    """Count one full rebuild a stale (or unusable) entry fell to."""
    REGISTRY.inc("tidb_tpu_delta_declines_total", {"gate": gate})
    if timeline.ENABLED:
        timeline.instant("delta.decline", "delta",
                         args={"gate": gate, "table": table_id})


class Declined(Exception):
    def __init__(self, gate: str):
        super().__init__(gate)
        self.gate = gate


# ---------------------------------------------------------------------------
# the ledger: which row of which region sits where on the device
# ---------------------------------------------------------------------------

def ledger_from_coverage(cov):
    """The base build's coverage → (`seen`, `rowmap`): the Region object
    last diffed per region id, and per region its row ranges with where
    they sit — (row_start, row_stop, in_delta, offset, alive-at-build or
    None): a base range's row j is at offset + (its rank among the rows
    alive at build), a delta range's at offset + (j - row_start)."""
    seen, rowmap = {}, {}
    for rid, n_rows, alive, base_off, region in cov:
        if region is None:
            return None, None
        seen[rid] = region
        rowmap[rid] = ((0, n_rows, False, base_off,
                        None if alive is None or alive.all() else alive),)
    return seen, rowmap


def _positions(segs, rows: np.ndarray):
    """Region row numbers → (base positions, delta positions)."""
    base, delta = [], []
    for start, stop, in_delta, off, alive in segs:
        sel = rows[(rows >= start) & (rows < stop)]
        if not sel.size:
            continue
        if in_delta:
            delta.append(off + (sel - start))
        elif alive is None:
            base.append(off + (sel - start))
        else:
            # rank among the rows alive at build (a row dead at build
            # cannot die again: the diff only hands over fresh deaths)
            rank = np.cumsum(alive) - 1
            base.append(off + rank[sel - start])
    return base, delta


def _diff(ent, td, scope):
    """What changed between the entry's ledger and `td` → (appended
    [(region, row_start, row_stop, delta offset)], dead base positions,
    dead delta positions, seen, rowmap, delta_rows). Raises Declined."""
    seen, rowmap = dict(ent.seen), dict(ent.rowmap)
    cursor = ent.delta_rows
    appended, dead_base, dead_delta = [], [], []
    present = set()
    for r in td.regions:
        if scope is not None and r.part is not None and r.part not in scope:
            continue
        prev = seen.get(r.id)
        if prev is None:
            if r.id <= ent.max_rid:
                # an OLD region this build never saw — deletes reset its
                # partition tag to None, pulling it into scope
                raise Declined("region-rescoped")
            n = r.num_rows
            appended.append((r, 0, n, cursor))
            rowmap[r.id] = ((0, n, True, cursor, None),)
            if r.live_rows != n:
                dead_delta.append(cursor + np.flatnonzero(r.deleted))
            cursor += n
            seen[r.id] = r
            present.add(r.id)
            continue
        present.add(r.id)
        if r is prev:
            continue
        n_prev = prev.num_rows
        if r.num_rows < n_prev:
            raise Declined("region-shrank")
        grew = r.num_rows - n_prev
        if r.live_rows - grew != prev.live_rows:
            now = np.asarray(r.deleted[:n_prev])
            if prev.live_rows != n_prev and (prev.deleted & ~now).any():
                raise Declined("row-resurrected")
            fresh = np.flatnonzero(now & ~prev.deleted) \
                if prev.live_rows != n_prev else np.flatnonzero(now)
            if fresh.size:
                b, d = _positions(rowmap[r.id], fresh)
                dead_base += b
                dead_delta += d
        if grew:
            appended.append((r, n_prev, r.num_rows, cursor))
            rowmap[r.id] = rowmap[r.id] + ((n_prev, r.num_rows, True,
                                            cursor, None),)
            tail = np.asarray(r.deleted[n_prev:])
            if tail.any():
                dead_delta.append(cursor + np.flatnonzero(tail))
            cursor += grew
        seen[r.id] = r
    if len(present) != len(seen):
        raise Declined("regions-rewritten")
    cat = lambda parts: (np.concatenate(parts).astype(np.int64)  # noqa: E731
                         if parts else np.empty(0, dtype=np.int64))
    return appended, cat(dead_base), cat(dead_delta), seen, rowmap, cursor


def _rows_of(appended, scan, col_idx: int):
    """ONE column of the appended rows → (vals, valid), aligned to the
    scan's schema (DDL-padded)."""
    vals, valid = [], []
    for r, start, stop, _off in appended:
        col = align_chunk_to_schema(r.chunk, scan.table).columns[col_idx]
        vals.append(col.values[start:stop])
        valid.append(col.valid_mask()[start:stop])
    if len(vals) == 1:
        return vals[0], valid[0]
    return np.concatenate(vals), np.concatenate(valid)


def _raw_chunk(ent, scan, i: int, ftype, appended):
    """Column `i` of the appended rows as the delta slab holds it: the
    values the base's programs DECODE to (dictionary codes for strings,
    the device float for DOUBLE, limb planes for wide decimals), raw."""
    from tidb_tpu.ops.jax_env import device_float_dtype
    vals, valid = _rows_of(appended, scan, i)
    if ftype.is_wide_decimal:
        return compress.wide_decimal_limbs(vals, ftype.wide_limb_count), valid
    if ftype.is_varlen:
        dictionary = ent.dicts.get(i)
        if dictionary is None:
            raise Declined("dictionary")
        folded = np.array([str(v) for v in vals], dtype=object)
        keys = dictionary
        if ftype.is_ci:
            folded, keys = fold_ci_array(folded), fold_ci_array(dictionary)
        codes = np.searchsorted(keys, folded).astype(np.int32) \
            if len(keys) else np.zeros(len(folded), dtype=np.int32)
        if valid.any():
            hit = np.clip(codes, 0, max(len(keys) - 1, 0))
            if not len(keys) or (keys[hit[valid]] != folded[valid]).any():
                # its code would mean another string to every program
                raise Declined("dictionary")
        return np.where(valid, codes, 0).astype(np.int32), valid
    if vals.dtype == np.dtype(np.float64):
        return vals.astype(np.dtype(device_float_dtype())), valid
    return np.ascontiguousarray(vals), valid


def _one_row(seen) -> tuple:
    """One row of the table as `_raw_chunk` takes appended rows: an empty
    delta slab's shapes and types are read off it."""
    return tuple((r, 0, 1, 0) for r in seen.values() if r.num_rows)[:1]


def _widen(bounds, lo: int, hi: int):
    """Bounds that hold [lo, hi] too. A side that is exceeded moves out
    geometrically (the span to the next power of two), so that a stream
    of growing keys changes the bounds — which the perfect-hash group
    domains and the joins' lookup tables are sized by, as trace constants
    — a logarithmic number of times."""
    b_lo, b_hi = bounds
    if lo >= b_lo and hi <= b_hi:
        return bounds
    if hi > b_hi:
        b_hi = b_lo + (1 << int(hi - b_lo).bit_length()) - 1
    if lo < b_lo:
        b_lo = b_hi - (1 << int(b_hi - lo).bit_length()) + 1
    return (b_lo, b_hi)


def _widen_for(ent, i: int, v: np.ndarray, m: np.ndarray) -> None:
    """Column `i`'s bounds on `ent`, widened to hold the appended integer
    values `v` where `m` (a dictionary-coded column keeps its code space)."""
    if v.dtype.kind in "iu" and v.ndim == 1 and m.any() \
            and ent.bounds.get(i) is not None and ent.dicts.get(i) is None:
        vv = v[m]
        ent.bounds[i] = _widen(ent.bounds[i], int(vv.min()), int(vv.max()))


def _pad_chunk(v: np.ndarray, m: np.ndarray):
    """Appended rows as the append program takes them: values and
    validity padded with zeros to a power-of-two bucket of rows."""
    n = int(m.shape[0])
    bucket = pow2(n, MIN_BUCKET)
    pv = np.zeros(v.shape[:-1] + (bucket,), dtype=v.dtype)
    pv[..., :n] = v
    pm = np.zeros(bucket, dtype=bool)
    pm[:n] = m
    return pv, pm


def pad_idx(pos: np.ndarray, cap: int) -> np.ndarray:
    """Positions as the mask programs take them: int32, padded with `cap`
    (dropped by the scatter) to a power-of-two bucket, or empty."""
    if not pos.size:
        return np.empty(0, dtype=np.int32)
    out = np.full(pow2(pos.size, MIN_BUCKET), cap, dtype=np.int32)
    out[:pos.size] = pos
    return out


# ---------------------------------------------------------------------------
# extension — the read-side half
# ---------------------------------------------------------------------------

def extend_entry(ctx, scan, ent, max_slab: int, phases=None,
                 masked: bool = False, quiet: bool = False,
                 private: bool = False, made=None, then=None):
    """Extend a stale cached entry into a NEW generation (sharing the
    base device arrays with `ent`), or count the decline and → None (the
    caller rebuilds). Never mutates `ent`. `masked`: a generation with
    liveness masks although nothing changed (what `_warm` runs the masked
    variants of a rebuilt table's programs over). `quiet`: → None
    without counting, for a caller that will not rebuild. `private`: no
    other thread can reach `ent` (a compaction's generation before the
    swap), so the statements' extensions do not wait for this one — which
    may cover minutes of writes in bucket sizes no statement ever
    compiled — and nothing is counted. `made`: () → the generation of
    this snapshot where another statement made it while this one waited
    for its turn (several connections meet one commit at once: the first
    extends, the others take what it made), else None; `then(generation)`
    is run before the next statement gets its turn (the install: a waiting
    statement must find what this one made)."""
    corrupted = failpoint.inject("delta-merge-stale")
    if corrupted is not None:
        raise LayoutError(
            f"delta extension diff failed validation "
            f"(failpoint: {corrupted!r}) — refusing the in-place merge")
    ph = phases if phases is not None else PhaseTimer()
    quiet = quiet or private
    with contextlib.nullcontext() if private else _EXT_LOCK:
        try:
            got = made() if made is not None else None
            if got is None:
                got = _extend_locked(ctx, scan, ent, max_slab, ph, masked)
            if then is not None:
                then(got)
            return got
        except LayoutError:
            raise
        except Declined as d:
            if not quiet:
                decline(d.gate, scan.table.id)
        except Exception:  # noqa: BLE001 — extension is best-effort:
            # any unexpected fault (a raced buffer delete, an exotic
            # chunk dtype) declines into the always-correct rebuild
            if not quiet:
                decline("error", scan.table.id)
    return None


def _extend_locked(ctx, scan, ent, max_slab, ph, masked=False):
    table_id = scan.table.id
    td = ctx.snapshot.table_data(table_id)
    if td is None or ent.seen is None or not ent.dev:
        raise Declined("no-coverage")
    pruned = getattr(scan, "partitions", None)
    scope = None if pruned is None else set(pruned)
    resident = sorted(ent.dev)
    ftypes = scan.schema.field_types
    if any(i >= len(ftypes) for i in resident):
        raise Declined("schema")
    with timeline.span("delta.diff", "delta", table=table_id,
                       regions=len(td.regions)):
        appended, dead_base, dead_delta, seen, rowmap, cursor = \
            _diff(ent, td, scope)
    cap, base_slabs = ent.slab_cap, ent.base_slabs
    n_new = cursor - ent.delta_rows
    dcap = ent.delta_cap or delta_capacity(cap)
    if cursor > dcap:
        raise Declined("delta-full")
    changed = bool(n_new or dead_base.size or dead_delta.size)

    new = dc.CachedTable(td, ent.max_slab, ent.total + n_new
                         - dead_base.size - dead_delta.size, cap,
                         ent.n_slabs, ent.parts, ent.n_cols,
                         compressed=ent.compressed)
    new.dicts, new.bounds = dict(ent.dicts), dict(ent.bounds)
    new.layouts, new.zmaps = dict(ent.layouts), dict(ent.zmaps)
    new.holes = dict(ent.holes)
    new.cov, new.base_slabs, new.base_total = \
        ent.cov, base_slabs, ent.base_total
    new.max_rid = max(ent.max_rid,
                      max((r.id for r in td.regions), default=-1))
    new.seen, new.rowmap = seen, rowmap
    new.base_td, new.lineage = ent.base_td, ent.lineage
    new.delta_version = int(getattr(ctx.snapshot, "version", 0) or 0)
    new.device, new.owners = ent.device, ent.owners
    new.pick_dev = ent.pick_dev     # (of the base build, as the stacks are)
    # (the base slabs by identity — a stacked column's ONE array — and a
    # delta slab of this generation's own)
    new.dev = {i: ent.dev[i].fork() for i in resident}
    new.alive, new.rows_override = ent.alive, ent.rows_override
    new.delta_cap, new.delta_rows = ent.delta_cap, ent.delta_rows
    new.dead_rows, new.is_delta = ent.dead_rows, ent.is_delta
    new.steps = ent.steps
    if not changed and not masked:
        # the write landed out of scope, or only touched rows this build
        # never covered: a pure REVALIDATION (same arrays, fresh td)
        return new

    tail = ent.n_slabs - 1      # the delta slab lives with the tail owner

    # appended rows: encode the new rows alone, write them into the
    # resident delta slab (made empty on the device at the first append)
    h2d = 0
    sample = ()
    if not n_new and not ent.delta_cap:
        # the delta slab belongs to a delta generation's shape from the
        # first change on, like the masks below: a generation that only
        # lost rows gets it empty (shapes and types read off one row), so
        # the first insert later makes no statement a program
        sample = _one_row(seen)
    if n_new or sample:
        with timeline.span("delta.encode", "delta", rows=n_new,
                           table=table_id), ph.phase("encode"):
            chunks = {i: _raw_chunk(ent, scan, i, ftypes[i],
                                    appended or sample) for i in resident}
            padded = {}
            for i, (v, m) in chunks.items():
                if not n_new:
                    v, m = v[..., :0], m[:0]
                padded[i] = _pad_chunk(v, m)
                _widen_for(new, i, v, m)
        if not ent.delta_cap:
            slabs = _on_slab(ent, tail, device_emit.emit_delta_alloc,
                             [(padded[i][0].shape[:-1], padded[i][0].dtype)
                              for i in resident], dcap)
            for i, t in zip(resident, slabs):
                new.dev[i].append(t)
            new.delta_cap = dcap
    if n_new:
        nbytes = sum(v.nbytes + m.nbytes for v, m in padded.values())
        with timeline.span("delta.upload", "delta", bytes=nbytes,
                           table=table_id), ph.phase("upload"):
            for i in resident:
                new.dev[i][base_slabs] = device_emit.emit_delta_append(
                    new.dev[i][base_slabs], padded[i][0], padded[i][1],
                    ent.delta_rows, n_new, dcap)
        h2d += nbytes
        new.delta_rows = cursor
        REGISTRY.inc("tidb_tpu_delta_rows_total", {"kind": "append"},
                     by=n_new)
    new.n_slabs = base_slabs + (1 if new.delta_cap else 0)

    # liveness: one mask a slab from the first change on, so that every
    # statement of a delta generation runs the masked variant of its slab
    # programs and none compiles when the first tombstone arrives later
    if ent.alive is not None:
        # (the base's masks under a structure of this generation's own: a
        # rewrite below leaves the older generation's as they were)
        alive = ent.alive.fork(own=True)
    else:
        alive = base_masks(ent, table_id)
    rows = dict(ent.rows_override) if ent.rows_override is not None else {
        s: ent.slab_rows(s) for s in range(base_slabs)}
    if new.delta_cap and len(alive) == base_slabs:
        alive.append(_on_slab(ent, tail, device_emit.emit_alive_init, 0,
                              dcap))
        rows[base_slabs] = 0
    none = np.empty(0, dtype=np.int32)
    if alive.is_stacked and dead_base.size:
        # ONE rewrite of the stacked masks, whatever slabs the rows died in
        # (a generation's masks are one array: it is new whole — every
        # slab's mask is read and written, `base_slabs` of them). `rows`
        # stays what the yardstick defines, the LEAST a rewrite had to
        # move: the capacity of the slabs a row died in (`touched`)
        touched = int(np.unique(dead_base // cap).size)
        with timeline.span("delta.tombstone", "delta",
                           slab=int(dead_base[0] // cap),
                           tombs=int(dead_base.size), touched=touched,
                           rows=cap * touched, table=table_id):
            idx = pad_idx(dead_base, cap * base_slabs)
            alive.set_stack(device_emit.emit_alive_update(
                alive.stack_leaf(), none, idx, cap, stacked=True))
        h2d += idx.nbytes
    for s in sorted(set((dead_base // cap).tolist())):
        pos = dead_base[dead_base // cap == s] - s * cap
        rows[s] -= int(pos.size)
        if alive.is_stacked:
            continue
        with timeline.span("delta.tombstone", "delta", slab=int(s),
                           tombs=int(pos.size), rows=cap,
                           table=table_id):
            idx = pad_idx(pos, cap)
            alive[s] = device_emit.emit_alive_update(alive[s], none, idx,
                                                     cap)
        h2d += idx.nbytes
    if n_new or dead_delta.size:
        born = pad_idx(np.arange(ent.delta_rows, cursor), dcap)
        idx = pad_idx(dead_delta, dcap)
        with timeline.span("delta.tombstone", "delta", slab=base_slabs,
                           tombs=int(dead_delta.size), rows=dcap,
                           table=table_id):
            alive[base_slabs] = device_emit.emit_alive_update(
                alive[base_slabs], born, idx, dcap)
        h2d += born.nbytes + idx.nbytes
        rows[base_slabs] += n_new - int(dead_delta.size)
    n_dead = int(dead_base.size + dead_delta.size)
    if n_dead:
        REGISTRY.inc("tidb_tpu_delta_rows_total", {"kind": "tomb"},
                     by=n_dead)
    new.alive, new.rows_override = alive, rows
    new.dead_rows = ent.dead_rows + n_dead
    new.is_delta = True
    if not changed:
        return new
    if new.owners is not None:
        new.owners = (list(new.owners) + [new.owners[-1]]
                      * new.n_slabs)[:new.n_slabs]
    # what the FK-aligned joins need to follow this generation: the
    # appended rows by region (their key values are read there), the dead
    # rows' regions likewise
    new.steps = (ent.steps + ({
        "from": ent.td, "to": td, "appended": tuple(appended),
        "offset": ent.delta_rows, "n_new": n_new,
        "dead": _dead_rows_by_region(ent, seen) if n_dead else ()},))[
            -MAX_STEPS:]
    if h2d:
        ph.add_h2d(h2d, logical=h2d)
    ph.note_delta_rows(new.delta_rows, token=id(new))
    REGISTRY.inc("tidb_tpu_delta_extensions_total",
                 {"table": str(table_id)})

    cause = compaction_due(new)
    if cause is not None:
        store = getattr(ctx.snapshot, "store", None)
        if store is not None:
            key = (getattr(new, "device", 0), id(store), table_id,
                   None if pruned is None else tuple(pruned))
            schedule_compaction(store, key, scan, resident, max_slab,
                                dict(ctx.vars), cause)
    return new


def base_masks(ent, table_id) -> "dc.SlabColumn":
    """The liveness masks of `ent`'s base slabs, made on the device from
    their live prefixes. Where the base slabs can be held as ONE array
    (several of them, on one device, none lost) the masks are BORN so —
    one program, nothing filled, nothing through the host — whether or
    not a statement program has stacked the table's columns yet: a
    generation's masks then have one form for the table's whole life, and
    so has the program that rewrites them (a warm-up that ran it over a
    mask a slab would leave the stacked one to compile inside some later
    window). Else a mask a slab."""
    cap, n = ent.slab_cap, ent.base_slabs
    if n > 1 and ent.owners is None and not ent.lost:
        return dc.SlabColumn.born_stacked(
            _on_slab(ent, 0, device_emit.emit_alive_stack,
                     [ent.slab_rows(s) for s in range(n)], cap),
            (cap,), table_id)
    return dc.SlabColumn(
        _on_slab(ent, s, device_emit.emit_alive_init, ent.slab_rows(s), cap)
        for s in range(n))


def _on_slab(ent, s: int, fn, *args):
    """Run `fn` on the device that owns slab `s` of a pod entry."""
    d = ent.owners[s] if ent.owners is not None and s < len(ent.owners) \
        else ent.device
    h = dc.device_handle(d) if (ent.owners is not None or d) else None
    if h is None:
        return fn(*args)
    with jax.default_device(h):
        # committed there: what a later program makes of it stays
        return jax.device_put(fn(*args), h)


def _dead_rows_by_region(ent, seen):
    """[(region now, region before)] of the regions that lost rows in
    this step: the aligned joins read the dead rows' keys from them."""
    return tuple((r, ent.seen[rid]) for rid, r in seen.items()
                 if rid in ent.seen and r is not ent.seen[rid]
                 and r.live_rows - (r.num_rows - ent.seen[rid].num_rows)
                 != ent.seen[rid].live_rows)


def delta_column(ent, scan, i: int, ftype):
    """The delta slab of a column the generation did not hold yet, from
    the rows its ledger says were appended → (vals, mask) on the device."""
    appended = sorted(
        ((ent.seen[rid], start, stop, off)
         for rid, segs in ent.rowmap.items()
         for start, stop, in_delta, off, _a in segs if in_delta),
        key=lambda t: t[3])
    rows = appended or _one_row(ent.seen)
    if not rows:
        raise Declined("no-coverage")
    v, m = _raw_chunk(ent, scan, i, ftype, rows)
    if not appended:
        v, m = v[..., :0], m[:0]    # (only rows died so far: it is empty)
    _widen_for(ent, i, v, m)   # (its bounds were taken from the base's rows)
    n = int(m.shape[0])
    pv, pm = _pad_chunk(v, m)
    slab = _on_slab(ent, ent.base_slabs, device_emit.emit_delta_alloc,
                    [(pv.shape[:-1], pv.dtype)], ent.delta_cap)[0]
    return device_emit.emit_delta_append(slab, pv, pm, 0, n,
                                         ent.delta_cap), pv.nbytes + pm.nbytes


def compaction_due(ent) -> Optional[str]:
    """Why this generation should be compacted, from sizes it knows."""
    if ent.delta_cap and ent.delta_rows >= COMPACT_FILL * ent.delta_cap:
        return "delta-fill"
    if ent.dead_rows and ent.dead_rows >= COMPACT_DEAD * max(
            ent.base_total, 1):
        return "dead-rows"
    return None


# ---------------------------------------------------------------------------
# async compaction — rebuild the base in idle heavy-batch slots
# ---------------------------------------------------------------------------

_PENDING: Dict[tuple, dict] = {}
_PENDING_LOCK = threading.Lock()
_DRAIN_LOCK = threading.Lock()
_WORKER: Optional[threading.Thread] = None


class _IdleGuard:
    """Batch-class admission token for the compaction worker: it queues
    like the heaviest batch statement, so interactive and cheap-batch
    work always ranks ahead — compaction runs in idle slots."""

    sched_class = "batch"
    sched_cost = 1e9
    conn_id = -7

    def __init__(self):
        self.queue_wait_s = 0.0
        self.queue_waits = 0

    def check(self, site: str) -> None:
        pass


def schedule_compaction(store, key, scan, cols, max_slab: int,
                        vars_: dict, cause: str = "delta-fill") -> None:
    """Queue one compaction job per cache key (newest wins) and make
    sure a worker will drain it (unless tidb_tpu_compaction=off — the
    queue still fills, tests and tools drain it via
    run_pending_compactions)."""
    job = {"store": weakref.ref(store), "key": key, "scan": scan,
           "cols": list(cols), "max_slab": max_slab, "vars": vars_,
           "cause": cause}
    with _PENDING_LOCK:
        _PENDING[key] = job
    if var_on(vars_, "tidb_tpu_compaction"):
        _ensure_worker()


def pending_compactions() -> int:
    with _PENDING_LOCK:
        return len(_PENDING)


def forget_store(store, timeout_s: float = 10.0) -> None:
    """An engine closes: its queued compactions are dropped, and the one in
    flight is waited for (bounded) — a compaction left running goes on
    compiling and warming in a process whose next engine's statements it
    has nothing to do with."""
    with _PENDING_LOCK:
        for key in [k for k, job in _PENDING.items()
                    if job["store"]() in (store, None)]:
            del _PENDING[key]
    if threading.current_thread() is not _WORKER \
            and _DRAIN_LOCK.acquire(timeout=timeout_s):
        _DRAIN_LOCK.release()


def _pop_job():
    with _PENDING_LOCK:
        if not _PENDING:
            return None
        key = next(iter(_PENDING))
        return _PENDING.pop(key)


def _ensure_worker() -> None:
    global _WORKER
    with _PENDING_LOCK:
        if _WORKER is not None and _WORKER.is_alive():
            return
        _WORKER = threading.Thread(target=_worker_loop,
                                   name="tidb-tpu-compactor", daemon=True)
        _WORKER.start()


def _worker_loop() -> None:
    while True:
        with _DRAIN_LOCK:
            job = _pop_job()
            if job is None:
                return
            try:
                _compact_one(job)
            except Exception:  # noqa: BLE001 — a failed compaction
                # (including an injected compaction-commit fault) leaves
                # the old generation serving; the next extension that
                # finds compaction due re-schedules
                pass


def run_pending_compactions() -> int:
    """Synchronously drain the compaction queue (tests, tools, chaos) —
    → jobs that committed. Faults are swallowed per job: the old
    generation keeps serving and the job is consumed."""
    done = 0
    with _DRAIN_LOCK:
        while True:
            job = _pop_job()
            if job is None:
                return done
            try:
                if _compact_one(job):
                    done += 1
            except Exception:  # noqa: BLE001 — see _worker_loop
                pass


def _compact_one(job) -> bool:
    """Rebuild the job's cache entry from a snapshot with freshly
    re-chosen layouts + zone maps, then swap it in. The table may have
    moved on since the snapshot: the rebuilt generation is then stale on
    arrival, and the next read extends it from its own ledger. The
    `compaction-commit` failpoint sits between the finished rebuild and
    the swap: a fault there deletes the rebuilt buffers and leaves the
    old generation serving byte-exactly."""
    store = job["store"]()
    if store is None:
        return False
    key, scan = job["key"], job["scan"]
    table_id = scan.table.id
    snapshot = store.snapshot()
    td = snapshot.table_data(table_id)
    if td is None:
        return False
    with dc.LOCK:
        cur = dc.CACHE.get(key)
    if cur is None or not getattr(cur, "is_delta", False):
        return False    # evicted, or already rebuilt fresh — nothing to do
    guard = _IdleGuard()
    new = pv = None
    try:
        # admission in the batch class: the rebuild STARTS when no
        # statement waits for the device. Its host encode and uploads then
        # run slot-free, as a statement's streamed first touch does — a
        # slot is for dispatching programs, the rebuild dispatches none,
        # and held through the encode it kept every statement waiting for
        # as long as the rebuild took (0.5 s a table of 0.6M rows, chip)
        with scheduler.SCHEDULER.slot(guard=guard, conn_id=guard.conn_id):
            pass
        with timeline.span("compact.run", "delta", table=table_id,
                           cause=job.get("cause", ""),
                           rows=int(td.live_rows)):
            ctx = ExecContext(snapshot=snapshot, vars=dict(job["vars"]))
            ph = PhaseTimer()
            parts, total, cov, max_rid = dc.collect_parts(ctx, scan,
                                                           coverage=True)
            slab_cap = pow2(min(total, job["max_slab"]), lo=1024) if total \
                else 1024
            n_slabs = (total + slab_cap - 1) // slab_cap
            new = dc.CachedTable(td, job["max_slab"], total, slab_cap,
                                 n_slabs, parts, cur.n_cols,
                                 compressed=cur.compressed)
            new.device = getattr(cur, "device", 0)
            if new.device < 0:
                nd = max(scheduler.pool_devices(ctx), 1)
                new.owners = [min(s * nd // max(n_slabs, 1), nd - 1)
                              for s in range(n_slabs)]
            new.set_coverage(cov, max_rid)
            new.delta_version = int(getattr(snapshot, "version", 0) or 0)
            ftypes = scan.schema.field_types
            cols = [i for i in job["cols"] if i < len(ftypes)]
            with dc.beside_statements():
                if total:
                    preps = {}
                    for i in cols:
                        # _col_prep re-runs choose_layout under the CURRENT
                        # workload hints — the compaction-time layout
                        # re-search of arXiv 2112.13099
                        preps[i] = dc.col_prep(new, i, ftypes[i])
                        _keep_what_still_fits(preps[i], cur, i)
                        new.dicts[i] = preps[i]["dict"]
                        new.bounds[i] = preps[i]["bounds"]
                        new.layouts[i] = preps[i]["layout"]
                        if new.compressed:
                            zm = dc.col_zone_stats(new, preps[i])
                            if zm is not None:
                                new.zmaps[i] = zm
                    for _ in dc.stream_slabs(ctx, new, None, cols, preps, ph):
                        pass
            timeline.tag(slabs=n_slabs)
            pv = _warm(store, key, scan, new, job["max_slab"])
            new = pv.ent
            failpoint.inject("compaction-commit")
            with timeline.span("compact.swap", "delta", table=table_id):
                with dc.LOCK:
                    installed = dc.CACHE.get(key)
                    if installed is None or \
                            installed.lineage != cur.lineage:
                        # evicted, or rebuilt by a statement meanwhile:
                        # nothing of ours is wanted any more
                        raise _StaleRebuild()
                    dc.install_preview(pv)
                # the replaced generation's buffers free NOW unless a live
                # statement still computes on them (protect discipline)
                dc.safe_delete(installed, key[1:3])
    except BaseException:
        if new is not None:
            new.delete()    # exclusively owned — frees HBM immediately
        for built in (pv.aligned.values() if pv is not None else ()):
            built.delete()
        raise
    REGISTRY.inc("tidb_tpu_compactions_total",
                 {"table": str(table_id), "cause": job.get("cause", "")})
    return True


def _warm(store, key, scan, new, max_slab: int):
    """Before the swap, on the compactor's thread: every fragment that
    lately read this table runs once over the rebuilt generation, brought
    to the store's newest snapshot first (the table moved on while it was
    rebuilt, and the statements after the swap will read such an
    extension). Re-chosen layouts mean new programs; they trace and
    compile HERE, where nobody waits, and the aligned structures over the
    new row positions are built here too (`device_cache.Preview`). A
    reader whose statement's text is known runs TWICE, under a guard that
    carries the text: the first run settles the digest's specialization
    over the rebuilt shapes, the second is the ONE statement program the
    statements after the swap will launch (`agg_slabs._StatementProgram`).
    How a reader is run again came with it (`device_cache.note_reader`:
    the executor above the cache hands the cache a callable, the cache's
    write path imports nothing above itself).
    A reader that cannot be warmed is skipped: the statement then pays
    what it would have paid. → the preview to install."""
    pv = dc.Preview(key, new)
    table_id = scan.table.id

    def follow(ctx) -> bool:
        if ctx.snapshot.table_data(table_id) is not pv.ent.td:
            nxt = extend_entry(ctx, scan, pv.ent, max_slab, private=True)
            if nxt is None:
                return False
            pv.ent = nxt
        return True

    # (a pod entry's slabs live on several devices under placement the
    # statements' admission makes: not warmed)
    for plan, vars_, sql, run in \
            dc.readers(key[1], table_id) if key[0] >= 0 else ():
        # a plain generation (nothing written meanwhile) is read a second
        # time under masks and with an empty delta slab: the variants the
        # first write after the swap will ask for
        for masked in (False, True):
            # the newest snapshot each time: the other tables a reader
            # joins are the shared cache's, and only ever move forward.
            # No admission slot: the launches are a few, and a compile
            # inside one would hold every statement up while it lasts
            # (twice: the first step may cover minutes of writes, and the
            # statement has to run at a snapshot no older than what the
            # connections have made of the other tables by now)
            for _ in range(2):
                guard = ExecutionGuard(sql=sql) if sql else None
                ctx = ExecContext(snapshot=store.snapshot(), vars=vars_,
                                  guard=guard, unscheduled=True)
                ctx.phases.device_index = key[0]    # the entry's device
                if guard is not None:
                    guard.device_index = key[0]
                if not follow(ctx):
                    return pv       # it cannot follow: swap what there is
            swap = pv.ent
            if masked and swap.is_delta:
                continue
            with timeline.span("compact.warm", "delta", table=table_id,
                               root=plan.root.name, masked=masked):
                try:
                    if masked:
                        pv.ent = extend_entry(ctx, scan, swap, max_slab,
                                              masked=True,
                                              private=True) or swap
                    for _rep in range(2 if sql else 1):
                        with pv:
                            run(plan, ctx)
                except Exception as e:  # noqa: BLE001 — best effort
                    timeline.tag(skipped=type(e).__name__)
                finally:
                    pv.ent = swap
    return pv


def _keep_what_still_fits(prep: dict, cur, i: int) -> None:
    """Bounds and a packed frame of reference are constants of the
    compiled programs: where the rebuilt column still fits the replaced
    generation's, it keeps them, and the statements their programs."""
    old_b, new_b = cur.bounds.get(i), prep.get("bounds")
    if old_b is not None and new_b is not None and prep["dict"] is None \
            and old_b[0] <= new_b[0] and new_b[1] <= old_b[1]:
        prep["bounds"] = old_b
    old, new = cur.layouts.get(i), prep.get("layout")
    if old is not None and new is not None and prep["kind"] == "num" \
            and old.kind == new.kind == "pack" and old.dtype == new.dtype \
            and old.sig() != new.sig() and new_b is not None \
            and new_b[0] >= old.ref \
            and new_b[1] - old.ref < (1 << old.width):
        prep["layout"] = old


class _StaleRebuild(Exception):
    pass
