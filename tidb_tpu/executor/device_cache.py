"""HBM-resident table cache — the device engine's columnar replica.

The reference never re-ships table data per query: TiFlash keeps a columnar
replica synced from the row store and MPP queries read it in place. The TPU
analog is this cache: the first device query against a table dictionary-
encodes its string columns, pads rows into power-of-two slabs, and uploads
each used column to HBM ONCE. Subsequent queries reuse the device arrays
directly — the per-query host work drops to slicing prepared values, and the
HBM copy is invalidated precisely when the table changes.

Invalidation rides the storage engine's immutability discipline
(tidb_tpu/storage): every committed write replaces the table's `TableData`
tuple, so identity (`is`) of the snapshot's TableData is an exact freshness
token — no version counters, no false sharing between tables. Reads inside
an open transaction bypass the cache (staged rows are session-private, the
UnionScan view).

Ref: TiFlash replica selection (planner/core/find_best_task.go reads
TiFlash availability per table); coprocessor cache
(store/copr/coprocessor_cache.go) is the reference's other read-cache
precedent.

One key, two generations. A key's entry is the NEWEST generation any
statement has read; behind it the entry keeps the generation it superseded
(`CachedTable.kept`, found by the identity of its `TableData`): a statement
whose snapshot was taken before a commit that another connection's statement
has already extended the cache past is served from the generation of ITS
snapshot, with no rebuild and no upload of resident rows. Generations of one
base build share every base array; a kept one owns only what the commit
replaced (the changed slabs' liveness masks, or the delta slab's arrays), so
it is bounded by a count and by those bytes (`KEPT_GENERATIONS`,
`KEPT_BYTES`) and dropped once the store's history cannot hand its
`TableData` to a snapshot any more. The entry never moves backwards: a
snapshot older than what is kept gets the counted plain rebuild BESIDE the
cache (gate `behind`, a slot of its own). The FK-aligned join structures keep
theirs the same way (`AlignedJoin.kept`), paired by the `TableData` of ONE
snapshot. `tidb_tpu_delta_generation_reads_total{age=}` says what every
cached read was served from.

Pod-scale serving shards this cache BY DEVICE: keys carry the owning
pool device index — `(dev, store_id, table_id, parts)` — each entry's
arrays are committed to that device via jax.device_put, and the HBM
budget / MAX_CACHED_TABLES caps are enforced per device (eight pool
members have eight HBMs). Small tables replicate lazily: each device
builds its own copy on first touch, so a dimension table ends up
resident wherever its queries land. Fact tables at or above
`tidb_tpu_partition_min_rows` build ONE pod-partitioned entry under
dev == -1 whose slab ranges are owned by contiguous device spans
(`CachedTable.owners`) — zone maps stay host-side per owner, and the
scheduler never steals a statement whose partitioned working set lives
elsewhere (locate_tables is its oracle).
"""

from __future__ import annotations

import math
import threading
import time
from collections import OrderedDict, deque
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

import numpy as np

from tidb_tpu.chunk import Column, compress
from tidb_tpu.chunk.device import encode_strings
from tidb_tpu.errors import DeviceLost, LayoutError
from tidb_tpu.executor import device_emit, scheduler, zonemap
from tidb_tpu.executor.scan import align_chunk_to_schema
from tidb_tpu.ops.jax_env import jax, jnp, lax
from tidb_tpu.storage import Snapshot
from tidb_tpu.sysvars import var_int, var_on
from tidb_tpu.types import fold_ci_array
from tidb_tpu.util import failpoint, phases as _ph, timeline
from tidb_tpu.util.escalation import pow2
from tidb_tpu.util.observability import REGISTRY, first_touch
from tidb_tpu.util.phases import PhaseTimer

MAX_CACHED_TABLES = 4       # PER DEVICE — each pool member's own cap
# older generations kept behind a key's newest one, for the statement whose
# snapshot another connection's commit-and-read has overtaken. ONE: under
# three query streams beside a refresh stream a statement arrives behind the
# cache about once in a thousand and never by more than a commit (PERF.md,
# PR 40: a commit lands every 250 ms, a statement takes 30); a reader further
# behind (`AS OF TIMESTAMP` minutes back) gets the rebuild beside. And at
# most this many bytes of what it owns beyond the arrays the newest
# generation holds too (liveness masks, the delta slab's arrays; a key's
# aligned structures: the lookup table, the changed slabs' match masks)
KEPT_GENERATIONS = 1
KEPT_BYTES = 128 << 20


class Stacked:
    """One leaf of a column's base slabs held as ONE device array with a
    leading axis of slabs (`SlabColumn.stacked`), as a program takes it:
    the stack `a`, and which of its rows the program reads. A statement
    program's loop reads a row a turn — `which` names the vector of stack
    rows (one a distinct set of resident slabs, `SlabPicks.vectors`) that
    turn `k` indexes; a program of ONE slab takes the slab's `row` beside
    the stack, a device scalar (`SlabColumn.at`: which slab is an
    argument, so six slabs are one program); a program that reads the
    table whole asks for rows it knows (`WholeColumn`). Each is `slab(row)`:
    an index of the stack inside the fusion that reads the slab. `shape`:
    the leaf's own in ONE slab — a stack holds a 1-D leaf folded
    (`device_emit.folded`), and a slab of it is reshaped back, which is a
    bitcast. A pytree node: the array and `row` (or None) are its
    children, `which` and `shape` static."""

    __slots__ = ("a", "row", "which", "shape")

    def __init__(self, a, row, which: int, shape: tuple):
        self.a, self.row, self.which, self.shape = a, row, which, shape

    def children(self):
        return (self.a, self.row), (self.which, self.shape)

    def slab(self, row):
        """Row `row` of the stack — a traced scalar, or a number — as the
        leaf in its own shape (traced)."""
        return device_emit.unfold(
            self.a[row] if isinstance(row, int) else
            lax.dynamic_index_in_dim(self.a, row, 0, keepdims=False),
            self.shape)


_NODES: set = set()


def _node(cls):
    """`cls` — `Stacked`, `WholeColumn` — registered as a pytree node on
    first use: `children` → (its
    arrays, what is static), `cls(*children, *static)` back."""
    if cls not in _NODES:
        jax.tree_util.register_pytree_node(
            cls, cls.children, lambda aux, kids: cls(*kids, *aux))
        _NODES.add(cls)
    return cls


def in_place(tree, picks=(), k=None):
    """A program's arguments with what stands for slabs of stacked columns
    resolved INSIDE its trace — slices of the stacks that fuse into what
    reads them: a `Stacked` leaf as its slab (the row it came with, else
    turn `k`'s of a statement program's `picks`), a `WholeColumn` as its
    list of slabs. The statement programs' loop
    (`agg_slabs._StatementProgram`), the slab programs
    (`agg_slabs._FragmentProgram._partial`) and the tree programs
    (`TreeProgram._run`) call it first; anything else is handed `col[s]`,
    a copy (`tidb_tpu_slab_slices_total`)."""

    def read(x):
        if isinstance(x, WholeColumn):
            return x.slabs()
        if not isinstance(x, Stacked):
            return x
        if x.row is not None:
            return x.slab(x.row)
        return x.slab(picks[x.which][k]) if k is not None else x
    return jax.tree.map(
        read, tree, is_leaf=lambda x: isinstance(x, (WholeColumn, Stacked)))


def _stack_programs(n: int, shape: tuple, dtype: str):
    """→ (`first`, `put`): a new stack of `n` slabs of one leaf with its
    first slab in row 0 (allocated where that slab lies: the entry's
    device), and one slab written into a row of it — the stack DONATED, so
    the write is in place and a fill never holds two stacks (names of
    their own kind, `slab_stack_<sig8>`; which row is an argument: one
    `put` a leaf)."""
    key = (n, tuple(shape), dtype)

    def _put(stack, slab, row):
        return lax.dynamic_update_index_in_dim(
            stack, device_emit.fold(slab), row, 0)

    def _first(slab):
        return _put(jnp.zeros((n,) + device_emit.folded(tuple(shape)),
                              dtype=dtype), slab, 0)
    return (device_emit.delta_program("slab_stack", ("first",) + key,
                                       lambda: _first),
            device_emit.delta_program("slab_stack", ("put",) + key,
                                       lambda: _put, donate_argnums=(0,)))


def _write_slab(write, *args):
    """One slab into a stack (`first(slab)`, `put(stack, slab, row)`) →
    the stack, WRITTEN: dispatch runs ahead, and every slab a fill
    uploaded before its first write ended would lie beside the stack at
    once."""
    return jax.block_until_ready(write(*args))


def _on_host(a, device):
    """`a`'s bytes in host memory of their own. (The CPU backend hands
    out a view of the device buffer, which would keep it: copied there.)"""
    h = np.asarray(a)
    return h.copy() if device.platform == "cpu" else h


def _fill(col: list, device):
    """The resident slabs of ONE leaf (`col`, emptied as it goes) → their
    stack, holding a SLAB twice and never the leaf: the stack's own bytes
    must be free before its first row is written, and the slabs lie where
    they would have to be — so all but the first wait on the HOST (dropped
    from the device as each arrives there), the stack is allocated beside
    the one that stayed, and each goes in through a donated write (the
    first from where it lies, the others uploaded one at a time). On the
    device at any moment: the leaf's bytes and at most one slab's more.
    The arrays are dropped, never `.delete()`d: a launch in flight that
    took one still reads it."""
    first, put = _stack_programs(len(col), tuple(col[0].shape),
                                 str(col[0].dtype))
    # (the stack is committed to its device iff its slabs were — an
    # entry pinned to a pool member's; on one device nothing is, as at
    # the upload: a program compiles once an argument's commitment, and
    # a stack of another than the arrays around it compiles each twice)
    there = device if col[0].committed else None
    for r in range(1, len(col)):
        col[r] = _on_host(col[r], device)
    stack = None
    for r, slab in enumerate(col):
        if isinstance(slab, np.ndarray):
            slab = jax.device_put(slab, there)
        stack = _write_slab(first, slab) if r == 0 else \
            _write_slab(put, stack, slab, np.int32(r))
        col[r] = slab = None
    return stack


# a column's base slabs become one array under this lock (`SlabColumn.stack`:
# once a column and base build; a stacked column's readers take none)
_STACK_LOCK = timeline.named_lock("slab_stack")

# what `_BaseSlabs.slabs` reads while a fill holds the column's arrays
_IN_TRANSIT = object()


def _leaves(t) -> tuple:
    """The arrays of one slab of a column: a tuple's, or the one (a mask);
    none of a hole's."""
    return () if t is None else tuple(t) if isinstance(t, (tuple, list)) \
        else (t,)


def _count_slice(b: "_BaseSlabs") -> None:
    """A slab was SLICED out of a stacked column — a device copy of its
    arrays, for a per-slab consumer. Always-on counter
    `tidb_tpu_slab_slices_total{table=}`: 0 in a warm window of statements
    that run `launch_plan=whole` (nothing hides a copy a statement)."""
    REGISTRY.inc("tidb_tpu_slab_slices_total", {"table": b.table})


class _BaseSlabs:
    """The base slabs of one column: what every generation of one base
    build shares by identity. A list of per-slab pytrees (None = a hole)
    until `SlabColumn.stack` makes them ONE array a leaf — the stack IS
    the storage from then on, the per-slab arrays are dropped as they go
    in. Readers ask `lists()`: while a fill holds the arrays (`slabs` is
    `_IN_TRANSIT`) there is nothing to read, and they wait it out."""

    __slots__ = ("slabs", "leaves", "shared", "shapes", "treedef", "pos",
                 "table", "row_dev")

    def __init__(self, slabs):
        self.slabs = list(slabs)    # (None once stacked)
        # once stacked: the slab pytree's leaves, each with a leading axis
        # of the RESIDENT slabs and its 1-D rows folded
        # (`device_emit.folded`; a leaf every slab's tuple shares — a
        # dictionary — as it is: `shared`),
        # each leaf's own shape in one slab, the slab's tree structure,
        # and slab → row of the stack (None: a hole)
        self.leaves: Optional[list] = None
        self.shared: tuple = ()
        self.shapes: tuple = ()
        self.treedef = None
        self.pos: tuple = ()
        self.table = ""             # (for the counters: whose column)
        self.row_dev: dict = {}     # stack row → it, a device int32 scalar

    def tree(self, leaf):
        """One slab's pytree over the stacks: `leaf(stack, the leaf's own
        shape in one slab)` for every stacked leaf, a shared one as it is."""
        return self.treedef.unflatten(
            a if sh else leaf(a, shape)
            for a, sh, shape in zip(self.leaves, self.shared, self.shapes))

    def lists(self) -> Optional[list]:
        """The per-slab list, or None once the column is stacked (a fill
        in progress is over when its lock is free)."""
        slabs = self.slabs
        if slabs is _IN_TRANSIT:
            with _STACK_LOCK:
                slabs = self.slabs
        return slabs

    def copy(self) -> "_BaseSlabs":
        """The same arrays under a structure of its own: what a generation
        that CHANGES base slabs (liveness, match masks) writes into."""
        slabs = self.lists()
        new = _BaseSlabs(slabs or ())
        if slabs is None:
            new.slabs, new.leaves = None, list(self.leaves)
            new.shared, new.shapes, new.treedef, new.pos, new.table = \
                self.shared, self.shapes, self.treedef, self.pos, self.table
            new.row_dev = self.row_dev
        return new


class SlabColumn:
    """One resident column's slabs as the device holds them — a table
    column's packed or raw tuples, a generation's liveness masks, an
    aligned join's match masks or gathered build columns in the fact's row
    space. List-like for everything that reads slab `s`: `col[s]`,
    `len(col)`, iteration; the raw delta slab (a shape of its own) sits
    behind the base slabs at `col[n_base]`.

    The base slabs share one shape and never change after the build, so a
    column with more than one can be held as ONE array a leaf with a
    leading axis of slabs (`stack`, when the first statement program over
    the table is built: the code observes it, nothing switches it on; the
    stack is filled a slab at a time, `_fill`, so the device never holds
    a leaf's slabs beside their stack; a generation's liveness masks are
    MADE so at the table's first commit, `born_stacked`, columns stacked
    or not: one form for the table's life).
    From then on every PROGRAM reads a slab where it lies: `stacked()`
    hands a statement program the array itself (`Stacked` leaves its loop
    indexes), `at(s)` a per-slab program the array and the slab's row
    (`Stacked` too), `whole()` a program that reads the table whole the array
    and the rows (`WholeColumn`) — each resolved inside the program's
    trace. `col[s]` is a SLICE, a device copy, for what is no program of
    those (the aligned structures' one-off builds, the compactor, the
    delta path's host-driven steps), counted
    (`tidb_tpu_slab_slices_total`). A one-slab column is its own stack:
    nothing is copied, nothing indexed. Generations of one base build
    share the base (`fork`); what changes a commit (a mask) is a new
    stacked array (`fork(own=True)`, `set_stack`). An entry whose slabs
    lie on several devices (`owners`) or that lost one is never stacked
    (`SlabPicks.of`)."""

    __slots__ = ("base", "delta")

    def __init__(self, slabs=(), delta=None, base: _BaseSlabs = None):
        self.base = _BaseSlabs(slabs) if base is None else base
        self.delta = delta

    @classmethod
    def born_stacked(cls, stack, shape: tuple, table="") -> "SlabColumn":
        """A one-leaf column (a generation's liveness masks) whose base
        slabs a program MADE as one array (`stack`: slabs × a slab's leaf
        of `shape`, folded): nothing to fill, nothing crosses the host."""
        b = _BaseSlabs(())
        b.slabs, b.leaves, b.shared = None, [stack], (False,)
        b.shapes, b.treedef = (tuple(shape),), jax.tree.structure(0)
        b.pos, b.table = tuple(range(stack.shape[0])), str(table)
        return cls(base=b)

    def fork(self, own: bool = False) -> "SlabColumn":
        """This column for a later generation: the same base (by identity;
        `own`: under a structure that can be written to), its own delta
        slab."""
        return SlabColumn(delta=self.delta,
                          base=self.base.copy() if own else self.base)

    # -- list-like ----------------------------------------------------------
    @property
    def n_base(self) -> int:
        slabs = self.base.lists()
        return len(self.base.pos) if slabs is None else len(slabs)

    @property
    def is_stacked(self) -> bool:
        return self.base.lists() is None

    def __len__(self) -> int:
        return self.n_base + (self.delta is not None)

    def __iter__(self):
        return (self[s] for s in range(len(self)))

    def __getitem__(self, s):
        if isinstance(s, slice):
            return [self[i] for i in range(*s.indices(len(self)))]
        n = self.n_base
        if s < 0:
            s += len(self)
        if s == n and self.delta is not None:
            return self.delta
        if not 0 <= s < n:
            raise IndexError(s)
        b = self.base
        slabs = b.lists()           # (read once: `stack` swaps it for None)
        if slabs is not None:
            return slabs[s]
        row = b.pos[s]
        if row is None:
            return None
        _count_slice(b)
        return b.tree(lambda a, shape: device_emit.unfold(a[row], shape))

    def __setitem__(self, s: int, t) -> None:
        slabs = self.base.lists()
        if s == self.n_base:
            self.delta = t
        elif slabs is None:
            raise TypeError("a stacked base is rewritten by set_stack")
        else:
            slabs[s] = t

    def append(self, t) -> None:
        """The raw delta slab takes its place behind the base."""
        assert self.delta is None
        self.delta = t

    def holes(self) -> frozenset:
        slabs = self.base.lists()
        seq = self.base.pos if slabs is None else slabs
        return frozenset(s for s, t in enumerate(seq) if t is None)

    # -- accounting (no slice, no device work) ------------------------------
    def arrays(self):
        """(slab index, device array) of everything held, each array once
        (a stacked leaf under slab 0)."""
        b, seen = self.base, set()
        slabs = b.lists()
        per_slab = list(enumerate(slabs)) if slabs is not None else \
            [(0, b.leaves)]
        for s, t in per_slab + [(self.n_base, self.delta)]:
            for a in _leaves(t):
                if id(a) not in seen:
                    seen.add(id(a))
                    yield s, a

    def slab_nbytes(self, s: int) -> int:
        """Physical bytes a reader of slab `s` reads (0 for a hole), from
        shapes and dtypes alone."""
        b = self.base
        slabs = b.lists()
        if s >= self.n_base or slabs is not None:
            return _tuple_nbytes(
                _leaves(self.delta if s >= self.n_base else slabs[s]))
        if b.pos[s] is None:
            return 0
        return sum(math.prod(a.shape if sh else a.shape[1:])
                   * a.dtype.itemsize for a, sh in zip(b.leaves, b.shared))

    def specs(self) -> tuple:
        """(shape, dtype) of each leaf of ONE base slab (no slice)."""
        b = self.base
        slabs = b.lists()
        if slabs is None:
            return tuple((shape, a.dtype)
                         for shape, a in zip(b.shapes, b.leaves))
        one = next((t for t in slabs if t is not None), self.delta)
        return tuple((tuple(a.shape), a.dtype) for a in _leaves(one))

    # -- the stack ----------------------------------------------------------
    def stack(self, table="") -> None:
        """Hold the base slabs as ONE array a leaf from now on (no-op for
        a one-slab or an already stacked column): once a column and base
        build (`tidb_tpu_slab_stacks_total{table=}`, span `slab.stack`).
        From the first line under the lock the arrays are the fill's alone
        (`_IN_TRANSIT`: a reader waits) and go into their stack a leaf and
        a slab at a time (`_fill`), so no list lies beside its stack."""
        b = self.base
        slabs = b.lists()
        if slabs is None or len(slabs) < 2:
            return
        del slabs
        with _STACK_LOCK:
            slabs = b.slabs         # (under the lock: never in transit)
            if slabs is None:
                return
            pos, held = [], 0
            for t in slabs:
                pos.append(None if t is None else held)
                held += t is not None
            if not held:
                return
            flat = [jax.tree.flatten(t) for t in slabs if t is not None]
            treedef = flat[0][1]
            # leaf → its array a resident slab: the only references the
            # cache keeps from here on (frames of launches in flight aside)
            src = [list(col) for col in zip(*(lv for lv, _td in flat))]
            b.slabs = _IN_TRANSIT
            del slabs, flat, t
            shared = tuple(held > 1 and all(a is col[0] for a in col)
                           for col in src)
            shapes = tuple(tuple(col[0].shape) for col in src)
            device = next(iter(src[0][0].devices()))
            try:
                with timeline.span("slab.stack", "cache", table=table,
                                   slabs=held):
                    leaves = [col[0] if sh else _fill(col, device)
                              for col, sh in zip(src, shared)]
            except BaseException:
                # (the device is out of memory, or an interrupt: the
                # arrays went with the fill — nothing of the column is
                # resident, and `SlabPicks.of` drops the table's entries)
                b.slabs = [None] * len(pos)
                raise
            b.pos, b.shapes, b.shared = tuple(pos), shapes, shared
            b.leaves, b.treedef, b.table = leaves, treedef, str(table)
            b.slabs = None          # (last: readers look at it first)
        REGISTRY.inc("tidb_tpu_slab_stacks_total", {"table": str(table)})

    def stack_leaf(self):
        """The stack of a one-leaf column (a mask), or None if unstacked."""
        return None if self.base.lists() is not None \
            else self.base.leaves[0]

    def set_stack(self, leaf) -> None:
        """A one-leaf stacked column's stack, rewritten (a NEW array: the
        old one is another generation's; the base must be this
        generation's own, `fork(own=True)`)."""
        assert self.base.lists() is None
        self.base.leaves[0] = leaf

    def stacked(self, which: int = 0):
        """The base slabs as a statement program takes them: the slab's
        pytree with every stacked leaf a `Stacked`; a one-slab column's
        own arrays as they are."""
        b = self.base
        slabs = b.lists()
        if slabs is not None:
            assert len(slabs) == 1, "stack() first"
            return slabs[0]
        node = _node(Stacked)
        return b.tree(lambda a, shape: node(a, None, which, shape))

    def rows_of(self, ids) -> tuple:
        """The stack rows of slabs `ids` (stacked, all resident)."""
        return tuple(self.base.pos[s] for s in ids)

    def at(self, s: int):
        """Slab `s` for a PROGRAM that reads one slab (the per-slab plans,
        a digest's first execution, a filter or ORDER BY root): as `col[s]`
        — but of a stacked column the stack itself and the slab's row in
        it (`Stacked` leaves), which the program indexes inside its trace
        (`in_place`), where `col[s]` would copy the slab first."""
        b = self.base
        if b.lists() is not None or not 0 <= s < self.n_base \
                or b.pos[s] is None:
            return self[s]
        row = b.pos[s]
        idx = b.row_dev.get(row)
        if idx is None:
            idx = b.row_dev[row] = jax.device_put(
                np.int32(row), next(iter(b.leaves[0].devices())))
        node = _node(Stacked)
        return b.tree(lambda a, shape: node(a, idx, 0, shape))

    def whole(self):
        """Every slab, for a program that reads the table WHOLE (a build
        side, the mega-slab tree program): the list itself, or of a
        stacked column a `WholeColumn` — the program lists the slabs
        inside its trace (`in_place`), so no statement slices one."""
        slabs = self.base.lists()
        if slabs is not None:       # (every warm tree statement: no loop)
            return slabs + [self.delta] if self.delta is not None \
                else list(slabs)
        return _node(WholeColumn)(self.stacked(), self.delta, self.base.pos)


class WholeColumn:
    """A stacked column handed WHOLE to a program: its stacks (`base`, the
    slab's pytree of `Stacked` leaves), the raw delta slab's own arrays or
    None, and slab → row of the stacks (`rows`, static; None = a hole).
    `slabs()`, inside the trace, is the per-slab list the program read
    before the column was stacked: a STATIC index of the stack, which the
    compiler reads where it lies. A pytree node."""

    __slots__ = ("base", "delta", "rows")

    def __init__(self, base, delta, rows: tuple):
        self.base, self.delta, self.rows = base, delta, rows

    def children(self):
        return (self.base, self.delta), (self.rows,)

    def slabs(self) -> list:
        is_stack = lambda x: isinstance(x, Stacked)     # noqa: E731
        out = [None if row is None else jax.tree.map(
            lambda x, row=row: x.slab(row) if is_stack(x) else x,
            self.base, is_leaf=is_stack) for row in self.rows]
        return out if self.delta is None else out + [self.delta]


class SlabPicks:
    """Which stack rows the turns of ONE statement program index: the
    surviving base slabs `ids`, as one int32 vector a distinct position
    map among the columns it reads (one, unless first touches pruned
    columns differently: a column with holes stacks its resident slabs
    alone). `of(col)` stacks `col` if it is not yet and hands out its
    `stacked()` form, bound to its vector."""

    def __init__(self, ent: "CachedTable", ids, table=""):
        self.ent, self.ids, self.table = ent, tuple(ids), table
        self._rows: list = []

    def _which(self, rows: tuple) -> int:
        if rows not in self._rows:
            self._rows.append(rows)
        return self._rows.index(rows)

    def of(self, col: SlabColumn):
        if col.n_base == 1 and not col.is_stacked:
            return col.stacked()    # (a one-slab table: its own arrays)
        ent = self.ent
        if ent.owners is None and not getattr(ent, "lost", None):
            # (slabs on several devices are no one array, and a lost
            # slab's refill writes a slab: such an entry keeps its lists —
            # and takes the per-slab plan, `agg_slabs._launch_plan`)
            try:
                col.stack(self.table)
            except BaseException:
                # a fill that dies (the device out of memory, an
                # interrupt) takes the column's arrays with it: nothing
                # serves from this table's entries again, and the next
                # statement's open builds them anew
                if isinstance(self.table, int):
                    invalidate(self.table)
                raise
        if not col.is_stacked:
            return col.stacked()
        return col.stacked(self._which(col.rows_of(self.ids)))

    def live(self):
        """The base slabs' liveness as a slab's body takes it: the
        generation's masks, else the live prefixes' lengths — the entry's
        ONE device vector read like a stacked leaf (a one-slab table's:
        its scalar)."""
        ent = self.ent
        if ent.alive is not None:
            return self.of(ent.alive)
        if ent.base_slabs == 1:
            return ent.live_arg(0)
        return _node(Stacked)(ent.live_counts(), None,
                              self._which(self.ids), ())

    def vectors(self) -> tuple:
        return tuple(self.ent.pick_vector(r) for r in self._rows)


class CachedTable:
    """Per-table device payload: a `SlabColumn` a resident column (`dev`),
    one for the liveness masks of a delta generation (`alive`), and the
    dictionaries.

    A column's storage is its `SlabColumn`: per-slab tuples as the first
    touch uploaded them, until the first statement program over the table
    is built — from then on ONE device array a leaf with a leading axis of
    base slabs, which the program's loop indexes in place and which every
    generation of the base build shares by identity (the raw delta slab
    keeps its own arrays). Everything that reads slab `s` asks the column
    (`ent.dev[col][s]`: a slice of a stacked column, a device copy).

    With compression on, a column's slabs may be PACKED tuples
    (words, mask_words[, dictvals]) per chunk/compress.py — `layouts`
    records the per-column descriptor (None = raw), and the dictvals
    device array of a dict-layout column is the SAME object in every
    slab tuple (and stays one array beside the stack), so byte accounting
    and deletion dedupe it by identity.
    hbm_bytes() therefore charges PHYSICAL (compressed) bytes — the
    budget/eviction accounting sees what HBM actually holds, a stacked
    array once."""

    __slots__ = ("td", "max_slab", "total", "slab_cap", "n_slabs",
                 "parts", "dicts", "dev", "bounds", "n_cols", "layouts",
                 "compressed", "zmaps", "holes", "base_slabs",
                 "base_total", "delta_version", "rows_override", "is_delta",
                 "cov", "max_rid", "seen", "rowmap", "lineage", "base_td",
                 "alive",
                 "delta_cap", "delta_rows", "dead_rows", "steps",
                 "device", "owners", "lost", "live_dev", "pick_dev", "kept",
                 "kept_bytes")

    def __init__(self, td, max_slab: int, total: int, slab_cap: int,
                 n_slabs: int, parts, n_cols: int, compressed: bool = False):
        self.td = td                    # TableData identity token (or None)
        self.n_cols = n_cols            # schema width at build (DDL guard)
        self.max_slab = max_slab
        self.total = total
        self.slab_cap = slab_cap
        self.n_slabs = n_slabs
        self.parts = parts              # [(aligned chunk, alive or None)]
        self.compressed = compressed    # tidb_tpu_compression at build
        # -- delta-generation state (executor/delta.py) ------------------
        # base_slabs / base_total: slabs and rows of the immutable base as
        # built; no resident row ever moves. delta_cap: capacity of the
        # RAW delta slab at index base_slabs (0 until the first append);
        # delta_rows: rows written into it so far, dead ones included.
        # alive: per slab (base and delta) the device liveness mask, from
        # the first change on (None before: a live prefix). rows_override:
        # per-slab LIVE row counts beside the masks; dead_rows: rows dead
        # since the build. seen / rowmap: the ledger the next extension
        # diffs the current TableData against (delta.ledger_from_coverage).
        # lineage: a token all generations of one base build share — the
        # identity of the TableData it was built from, held as base_td so
        # that the id is not reused (the specialization cache keys it;
        # nothing of a generation is part of a program). delta_version: the store's commit version this
        # generation serves (micro-batches of one generation share it).
        # steps: what each extension since the build changed, for the
        # FK-aligned joins to follow.
        self.base_slabs = n_slabs
        self.base_total = total
        self.delta_version = 0
        self.rows_override: Optional[Dict[int, int]] = None
        self.is_delta = False
        self.cov = None    # [(rid, n_rows, alive or None, base_off, region)]
        self.max_rid = -1         # max region id across the WHOLE td
        self.seen: Optional[Dict[int, object]] = None
        self.rowmap: Optional[Dict[int, tuple]] = None
        self.base_td = td
        self.lineage = id(td)
        self.alive: Optional[SlabColumn] = None
        self.delta_cap = 0
        self.delta_rows = 0
        self.dead_rows = 0
        self.steps: tuple = ()
        # older generations of this base build, oldest first, while this
        # one is the key's newest (`_install_generation`), and the bytes
        # they own beyond what this one holds
        self.kept: tuple = ()
        self.kept_bytes = 0
        # pod-scale placement: the pool device index owning this entry's
        # arrays (-1 = pod-partitioned), and for pod entries the per-slab
        # owner device list (contiguous spans — slab s lives on owners[s])
        self.device = 0
        self.owners: Optional[List[int]] = None
        # slab indexes whose device arrays were LOST to a quarantined
        # pool member (evict_device nulled them and re-owned the range
        # onto survivors) — open_table refills EXACTLY these slabs on
        # next touch instead of re-streaming whole columns
        self.lost: set = set()
        # the slabs' live-row counts as DEVICE values, uploaded once a
        # generation (`live_arg`, `live_counts`): a generation is a new
        # CachedTable, so nothing here outlives the counts it was made of.
        # One vector of `n_slabs` int32 a DISTINCT set of pruned slabs:
        # zone maps prune runs of slabs, so at most n_slabs² / 2 of them
        self.live_dev: dict = {}
        # stack rows → the int32 device vector by which a statement
        # program's turns index the stacked columns (`pick_vector`): a few
        # bytes a distinct set of surviving slabs, shared by every
        # generation of this base build (no resident row ever moves)
        self.pick_dev: dict = {}
        self.dicts: Dict[int, Optional[np.ndarray]] = {}
        self.dev: Dict[int, SlabColumn] = {}  # col → its (vals, valid) slabs
        # col → ColLayout for packed columns; None/absent = raw layout
        self.layouts: Dict[int, Optional[object]] = {}
        # col → (lo, hi) over valid values; None for floats/empty — feeds
        # the perfect-hash group-by domain gate (agg_slabs.chain_key_bounds)
        self.bounds: Dict[int, Optional[Tuple[int, int]]] = {}
        # col → zonemap.ColumnZoneMap (compressed tables only): the
        # per-slab min/max/null-count ledger the host-side slab pruner
        # consults before any upload or dispatch
        self.zmaps: Dict[int, object] = {}
        # col → frozenset of slab ids whose device slabs are HOLES
        # (pruned away on cold first touch — dev[col][s] is None there);
        # a later statement whose prune set does not cover a column's
        # holes re-streams that column in full
        self.holes: Dict[int, frozenset] = {}

    def set_coverage(self, cov, max_rid: int) -> None:
        """Adopt a base build's coverage ledger (`collect_parts`)."""
        from tidb_tpu.executor import delta
        self.cov, self.max_rid = cov, max_rid
        self.seen, self.rowmap = delta.ledger_from_coverage(cov) \
            if cov is not None else (None, None)

    def slab_live(self, s: int):
        """What a slab program takes as the slab's liveness: the device
        mask of a delta generation (as a program takes a slab of it,
        `SlabColumn.at`), else the live prefix's length."""
        if self.alive is not None:
            return self.alive.at(s)
        return self.slab_rows(s)

    def _live_upload(self, key, make):
        """`make()`'s host counts on this entry's device, once a
        generation and `key` (two statements racing upload twice)."""
        got = self.live_dev.get(key)
        if got is None:
            got = self.live_dev[key] = jax.device_put(
                make(), device_handle(self.device))
        return got

    def pick_vector(self, rows: tuple):
        """`rows` (of the columns' stacks) as an int32 device vector: an
        upload a base build and distinct set, none a statement or commit."""
        got = self.pick_dev.get(rows)
        if got is None:
            got = self.pick_dev[rows] = jax.device_put(
                np.asarray(rows, dtype=np.int32), device_handle(self.device))
        return got

    def live_arg(self, s: int):
        """`slab_live` as the slab programs of an aggregate take it: the
        device mask, else the live prefix's length as a device int32
        scalar — every slab's in one upload a generation, none a launch."""
        if self.alive is not None:
            return self.alive.at(s)
        return self._live_upload("slabs", lambda: [
            np.int32(self.slab_rows(i)) for i in range(self.n_slabs)])[s]

    def live_counts(self, zeroed=frozenset()):
        """Every slab's live rows as ONE device int32 vector (what a tree
        program takes for a whole build side), the slabs zone maps pruned
        (`zeroed`) as 0: an upload a generation and pruned set."""
        return self._live_upload(frozenset(zeroed), lambda: np.array(
            [0 if i in zeroed else self.slab_rows(i)
             for i in range(self.n_slabs)], dtype=np.int32))

    def slab_mask(self, s: int):
        """The slab's liveness as a device mask, made from the live
        prefix where the generation holds none."""
        if self.alive is not None:
            return self.alive[s]
        return device_emit.emit_alive_init(self.slab_rows(s),
                                           self.slab_shape(s)[0])

    def slab_shape(self, s: int):
        """(capacity, is the raw delta slab) of slab `s`."""
        if self.delta_cap and s >= self.base_slabs:
            return self.delta_cap, True
        return self.slab_cap, False

    def _arrays(self):
        """(slab index, device array) of everything this generation holds:
        column slabs (a shared dictionary once) and liveness masks."""
        cols = list(self.dev.values())
        if self.alive is not None:
            cols.append(self.alive)
        for col in cols:
            yield from col.arrays()

    def resident(self, col: int, skip=frozenset()) -> bool:
        """Column `col` is usable for a statement skipping `skip`: its
        device slabs exist and any holes fall inside the skip set."""
        if col not in self.dev:
            return False
        return self.holes.get(col, frozenset()) <= skip

    def slab_rows(self, s: int) -> int:
        if self.rows_override is not None and s in self.rows_override:
            return self.rows_override[s]
        return min(self.slab_cap, self.total - s * self.slab_cap)

    def hbm_bytes(self) -> int:
        """What the generation holds, and what the kept ones behind it own
        beyond that."""
        return sum(a.nbytes for _s, a in self._arrays()) + self.kept_bytes

    def logical_bytes(self, cols=None) -> int:
        """Bytes the selected columns WOULD occupy uncompressed (raw
        columns and the raw delta slab: physical == logical)."""
        total = 0
        for i, slabs in self.dev.items():
            if cols is not None and i not in cols:
                continue
            lay = self.layouts.get(i)
            holes = slabs.holes()
            for s in range(len(slabs)):
                if s in holes:
                    continue
                if lay is None or self.slab_shape(s)[1]:
                    total += slabs.slab_nbytes(s)
                else:
                    total += compress.raw_slab_bytes(lay, self.slab_cap)
        return total

    def delete(self) -> None:
        """Free the device buffers NOW (donation discipline): an evicted
        entry must not keep HBM resident until the GC happens to run —
        a recompile right after eviction would otherwise double the
        high-water mark."""
        for _s, a in list(self._arrays()):
            _delete_array(a)
        self.dev.clear()
        self.alive = None


def _delete_array(a) -> None:
    try:
        a.delete()
    except Exception:  # noqa: BLE001 — already deleted / committed text
        pass


def _entry_delete(ent) -> None:
    """Free an evicted entry's device buffers (tolerates test doubles
    that stub hbm_bytes() without delete())."""
    if timeline.ENABLED:
        cur = _ph.current()
        try:
            freed = int(ent.hbm_bytes())
        except Exception:  # noqa: BLE001 — test doubles may stub this out
            freed = 0
        timeline.instant("evict", "cache",
                         pid=cur.conn_id if cur is not None else 0,
                         args={"bytes": freed})
    delete = getattr(ent, "delete", None)
    if delete is not None:
        delete()


# ---- the generations kept behind a key's newest one ------------------------
# (tables and aligned structures alike: `arrays(g)` → g's device arrays,
# `version(g)` → the store version of the snapshot it was made for)

def _table_arrays(g):
    """A generation's device arrays, each once (a stacked column's: the
    stack; `_keep_behind` tells what two generations share by identity)."""
    for _s, a in g._arrays():
        yield a


def _keep_behind(newest, older, arrays, live=None) -> None:
    """`older` (oldest first) become the generations kept behind `newest`.
    The oldest go while there are more than `KEPT_GENERATIONS`, or while
    what they own beyond `newest`'s arrays exceeds `KEPT_BYTES`; one that
    `live` says no snapshot can ask for any more goes at once. What goes is
    dropped, never deleted: it shares arrays with what stays, and its last
    reference frees what it alone held."""
    kept = [g for g in older if live is None or live(g)]
    kept = kept[max(len(kept) - KEPT_GENERATIONS, 0):]
    held = {id(a) for a in arrays(newest)}
    own = []
    for g in reversed(kept):                # the youngest claims first
        n = 0
        for a in arrays(g):
            if id(a) not in held:
                held.add(id(a))
                n += a.nbytes
        own.append(n)
    own.reverse()
    while kept and sum(own) > KEPT_BYTES:
        kept.pop(0)
        own.pop(0)
    newest.kept, newest.kept_bytes = tuple(kept), sum(own)


def _install_generation(tbl, key, cur, new, same, version, arrays,
                        live=None):
    """`new` takes its place under `key` of `tbl`, whose entry `cur` is
    None or of `new`'s base build (called under `LOCK`) → (the generation
    to serve, "newest" | "kept"). Newer than the entry: it becomes the
    entry, and the entry what is kept behind it. Older (its statement
    waited while others stepped the cache past its snapshot): it serves
    that statement alone and the entry stays — an entry never moves
    backwards. `same(g)`: another thread made this generation meanwhile,
    adopt its."""
    if cur is None:
        tbl[key] = new
        tbl.move_to_end(key)
        return new, "newest"
    for g in (cur,) + cur.kept:
        if same(g):
            return g, "newest" if g is cur else "kept"
    if version(new) <= version(cur):
        return new, "kept"
    older = cur.kept + (cur,)
    cur.kept, cur.kept_bytes = (), 0
    tbl[key] = new
    tbl.move_to_end(key)
    _keep_behind(new, older, arrays, live)
    _note_kept()
    return new, "newest"


def _generation_for(newest, version: int, usable):
    """A snapshot of store version `version` whose data is not `newest`'s
    → (the kept generation of that very data, None), else (None, `newest`)
    to extend from for a snapshot ahead of the cache, else (None, None):
    the snapshot is older than what is kept."""
    for g in newest.kept:
        if usable(g):
            return g, None
    return None, newest if newest.delta_version < version else None


def _generation_of(entry, td, lineage):
    """The generation of `td` among `entry` and those kept behind it, of
    the base build `lineage`, or None."""
    if entry is None or entry.lineage != lineage:
        return None
    return next((g for g in (entry,) + entry.kept if g.td is td), None)


class _OneCommit:
    """A statement's context as an extension reads it, at ONE commit's
    view of one table."""
    __slots__ = ("snapshot", "vars")

    def __init__(self, ctx, snapshot):
        self.snapshot, self.vars = snapshot, ctx.vars


def _commits_to(ctx, store, table_id: int, base, td, version: int) -> list:
    """The contexts to extend `base` through, one a commit that changed
    the table since `base`'s snapshot, the last of them `ctx` itself
    (whose snapshot holds `td`); `[ctx]` where the store's history does
    not reach back that far, and [] without a `base`."""
    if base is None:
        return []
    versions = getattr(store, "table_versions", None)
    path = [] if versions is None else \
        [(v, t) for v, t in versions(table_id, base.delta_version, version)
         if t is not base.td]
    if len(path) < 2 or path[-1][1] is not td:
        return [ctx]
    return [_OneCommit(ctx, Snapshot({table_id: t}, v, store))
            for v, t in path[:-1]] + [ctx]


def _store_holds(store, table_id: int):
    """ids of the `TableData` of `table_id` that a snapshot of `store` can
    still be handed, or None where the store does not say."""
    holds = getattr(store, "table_history", None)
    return None if holds is None else holds(table_id)


def _note_kept() -> None:
    """The gauges: generations kept behind the newest ones, and the bytes
    they own (called under `LOCK`)."""
    ents = [e for e in list(CACHE.values()) + list(_ALIGNED.values())
            if getattr(e, "kept", None)]    # (tests' doubles have none)
    REGISTRY.set_gauge("tidb_tpu_delta_generations_kept",
                       sum(len(e.kept) for e in ents))
    REGISTRY.set_gauge("tidb_tpu_delta_generations_kept_bytes",
                       sum(e.kept_bytes for e in ents))


def _count_read(age: str) -> None:
    """What a cached read was served from: the key's `newest` generation,
    one `kept` behind it, or a plain table `rebuilt` beside the cache."""
    REGISTRY.inc("tidb_tpu_delta_generation_reads_total", {"age": age})


CACHE: "OrderedDict[int, CachedTable]" = OrderedDict()
# FK-aligned join structures (see AlignedJoin below); keyed by join path
_ALIGNED: "OrderedDict[tuple, AlignedJoin]" = OrderedDict()

# ONE lock for all shared device-cache state (CACHE, _ALIGNED, the
# protection registry, eviction). RLock because eviction helpers are
# reachable from paths that already hold it. Expensive work — host scans,
# encoding, uploads, LUT builds — happens OUTSIDE the lock; only dict
# lookups/insertions/evictions are serialized, so concurrent first
# touches of DIFFERENT tables still overlap.
LOCK = timeline.named_lock("device_cache", reentrant=True)

# thread ident → frozenset of (store_id, table_id) pairs that thread's
# in-flight statement is actively computing on. The per-THREAD successor
# to the old per-ExecContext `_device_cache_protect` attribute: sibling
# sessions consult the union, so their evictions can never free device
# buffers another statement is mid-compute on.
_PROTECT: Dict[int, frozenset] = {}


# ---- what a compaction rebuilt, before the swap ---------------------------
# The compactor runs the statements that read its table ONCE over the
# rebuilt generation before it swaps it in (delta._warm), so the programs a
# re-chosen layout needs compile on its thread and not in the first
# statement after the swap. On that thread alone the table's key resolves
# to the rebuilt generation and aligned structures are built aside; the
# swap installs both.
_PREVIEW = threading.local()
MAX_READERS = 8
# (store id, table id) → the plans of the fragments that last read it
_READERS: Dict[Tuple[int, int], "OrderedDict[tuple, tuple]"] = {}


class PreviewMiss(Exception):
    """A warm-up statement would have had to change the shared cache."""


class Preview:
    def __init__(self, key, ent):
        self.key, self.ent = key, ent
        self.aligned: "OrderedDict[tuple, AlignedJoin]" = OrderedDict()

    def __enter__(self):
        _PREVIEW.cur = self
        return self

    def __exit__(self, *exc):
        _PREVIEW.cur = None


def _previewing() -> Optional[Preview]:
    return getattr(_PREVIEW, "cur", None)


def note_reader(store_id: int, table_ids, plan, vars_, what, run) -> None:
    """Remember `plan` (a device fragment that just ran) as a reader of
    its tables: what a compaction warms before its swap, by `run(plan,
    ctx)` — the executor's own way of running it once more. `what` tells
    one reader from another (the statement's text and the fragment's
    root: a table that moves is re-planned at every statement, and the
    newest plan of a statement stands for the older ones)."""
    if _previewing() is not None:
        return
    with LOCK:
        for tid in table_ids:
            seen = _READERS.setdefault((store_id, tid), OrderedDict())
            known = seen.get(what)
            if known is not None and known[0] is plan:
                seen.move_to_end(what)
                continue
            seen[what] = (plan, dict(vars_), run)
            seen.move_to_end(what)
            while len(seen) > MAX_READERS:
                seen.popitem(last=False)


def readers(store_id: int, table_id: int) -> list:
    """→ [(plan, vars, the statement's text or None, run)]."""
    with LOCK:
        return [(plan, vars_, what[0] if isinstance(what[0], str) else None,
                 run)
                for what, (plan, vars_, run) in
                _READERS.get((store_id, table_id), {}).items()]


def install_preview(pv: Preview) -> None:
    """The swap: the rebuilt generation takes its key and the structures
    built over it take theirs (called under `LOCK`)."""
    CACHE[pv.key] = pv.ent
    CACHE.move_to_end(pv.key)
    for akey, new in pv.aligned.items():
        old = _ALIGNED.get(akey)
        _ALIGNED[akey] = new
        _ALIGNED.move_to_end(akey)
        if old is not None and old is not new:
            safe_delete(old)
    pv.aligned.clear()


def _all_protected() -> frozenset:
    with LOCK:
        if not _PROTECT:
            return frozenset()
        out = set()
        for pairs in _PROTECT.values():
            out |= pairs
        return frozenset(out)


def _protected_elsewhere(pair) -> bool:
    """Whether a statement of ANOTHER thread computes on the table."""
    me = threading.get_ident()
    with LOCK:
        return any(pair in pairs for tid, pairs in _PROTECT.items()
                   if tid != me)


@contextmanager
def protect_tables(pairs):
    """Mark (store_id, table_id) pairs in active use by THIS thread for
    the duration — every device executor wraps its compute in this, so a
    sibling thread's budget/LRU eviction skips the entries and a stale-
    entry pop defers the buffer free to refcounting (below)."""
    tid = threading.get_ident()
    pairs = frozenset(pairs)
    with LOCK:
        prev = _PROTECT.get(tid)
        _PROTECT[tid] = pairs if prev is None else (prev | pairs)
    try:
        yield
    finally:
        with LOCK:
            if prev is None:
                _PROTECT.pop(tid, None)
            else:
                _PROTECT[tid] = prev


def safe_delete(ent, pair=None) -> None:
    """Free an evicted entry's device buffers — unless a concurrent
    statement may still be computing on them, in which case the explicit
    free is skipped and refcounting reclaims the arrays the moment the
    last in-flight reference drops (correctness over HBM promptness)."""
    if pair is not None:
        if pair in _all_protected():
            return
    elif _PROTECT:
        # derived entries (aligned joins) aren't tracked pair-wise: with
        # ANY statement in flight, defer to refcount reclamation
        return
    _entry_delete(ent)


def _drop_entry(key, ent) -> None:
    """Take `ent` out of the cache (if it is still what `key` holds) and
    free what no statement in flight computes on."""
    with LOCK:
        held = CACHE.get(key) is ent
        if held:
            CACHE.pop(key, None)
    if held:
        # (a generation kept behind the entry shares its arrays)
        safe_delete(ent, key[1:3])


def clear():
    with LOCK:
        cache = list(CACHE.items())
        aligned = list(_ALIGNED.values())
        CACHE.clear()
        _ALIGNED.clear()
        _READERS.clear()
        _note_kept()
    for k, e in cache:
        safe_delete(e, k[1:3])
    for e in aligned:
        safe_delete(e)


def invalidate(table_id: int):
    dead_c, dead_a = [], []
    with LOCK:
        for key in [k for k in CACHE if k[2] == table_id]:
            ent = CACHE.pop(key, None)
            if ent is not None:
                dead_c.append((key, ent))
        for key in [k for k, e in _ALIGNED.items()
                    if table_id in e.tds]:
            ent = _ALIGNED.pop(key, None)
            if ent is not None:
                dead_a.append(ent)
        _note_kept()
    for key, ent in dead_c:
        safe_delete(ent, key[1:3])
    for ent in dead_a:
        safe_delete(ent)


_STORE_FINALIZERS: Dict[int, object] = {}
# ids of collected stores, queued by their weakref finalizers. The
# collector runs a finalizer on whatever thread allocates next — possibly
# inside a `with LOCK` block that is iterating CACHE, or on a thread
# holding some other lock while a sibling holds LOCK — so the finalizer
# itself only appends here (atomic, lock-free) and the eviction happens
# at the next cache call that cares (_reap_dead_stores).
_DEAD_STORES: deque = deque()


def _reap_dead_stores() -> None:
    """Evict the entries of every store collected since the last call —
    run by the cache's entry points before they read CACHE, so a dead
    engine's tables are never looked up, counted against the budget, or
    left holding HBM past the next statement."""
    while _DEAD_STORES:
        try:
            store_id = _DEAD_STORES.popleft()
        except IndexError:              # a sibling thread drained it
            return
        _evict_store(store_id)


def _evict_store(store_id: int):
    with LOCK:
        dead_c = [(k, CACHE.pop(k)) for k in list(CACHE)
                  if k[1] == store_id]
        dead_a = [_ALIGNED.pop(k) for k in list(_ALIGNED)
                  if k[0] == store_id]
        _STORE_FINALIZERS.pop(store_id, None)
        for k in [k for k in _READERS if k[0] == store_id]:
            del _READERS[k]
        _note_kept()
    for key, ent in dead_c:
        safe_delete(ent, key[1:3])
    for ent in dead_a:
        safe_delete(ent)


# ---------------------------------------------------------------------------
# pod placement helpers — device pinning, partitioning, the locality oracle
# ---------------------------------------------------------------------------


def device_handle(idx):
    """jax.Device for pool member `idx`, or None when pinning is moot
    (single visible device, pod sentinel, index unknown) — callers fall
    back to the uncommitted jnp.asarray path, which is byte-identical to
    the pre-pod behavior."""
    if idx is None or idx < 0:
        return None
    try:
        devs = jax.devices()
    except Exception:  # noqa: BLE001 — no backend: pinning is moot
        return None
    if len(devs) <= 1:
        return None
    return devs[idx] if idx < len(devs) else devs[0]


def ctx_device(ctx) -> int:
    """The pool device index this statement is pinned to (stamped by
    scheduler placement on the guard, mirrored on the PhaseTimer for
    guard-less contexts); 0 when no placement ran — the single-device
    semantics."""
    guard = getattr(ctx, "guard", None)
    if guard is not None and getattr(guard, "device_index", None) is not None:
        return int(guard.device_index)
    ph = getattr(ctx, "phases", None)
    return int(getattr(ph, "device_index", 0) or 0)


def _approx_rows(td) -> int:
    """Row count from the region ledger — available BEFORE the host
    collect, so the partition decision can shape the cache key."""
    try:
        return sum(int(r.num_rows) for r in td.regions)
    except Exception:  # noqa: BLE001 — exotic TableData: never partition
        return 0


def _pod_partition(ctx, td) -> bool:
    if scheduler.pool_devices(ctx) <= 1:
        return False
    min_rows = var_int(ctx.vars, "tidb_tpu_partition_min_rows")
    return _approx_rows(td) >= max(min_rows, 1)


def locate_tables(table_ids, store_id: Optional[int] = None) \
        -> Dict[int, set]:
    """table_id → set of pool device indices currently holding a cached
    entry for it (-1 marks a pod-partitioned entry whose slab ranges
    span owner devices). The scheduler's locality oracle — a snapshot,
    advisory only: routing to a device that just evicted is a perf
    miss, never a correctness problem. `store_id` scopes the answer to
    one store (None = every store): table ids restart per engine, so an
    unscoped lookup lets engine A's resident table steer — or, when it
    is pod-partitioned, un-steal — engine B's statements."""
    want = set(table_ids)
    out: Dict[int, set] = {}
    with LOCK:
        keys = list(CACHE)
    for k in keys:
        if k[2] in want and (store_id is None or k[1] == store_id):
            out.setdefault(k[2], set()).add(k[0])
    return out


def replica_overhead_bytes() -> int:
    """HBM bytes spent on replica copies beyond the largest resident
    copy of each (store, table, parts) — the bench's replication-cost
    meter. Pod-partitioned entries hold one copy by construction."""
    _reap_dead_stores()
    with LOCK:
        entries = list(CACHE.items())
    groups: Dict[tuple, List[int]] = {}
    for k, e in entries:
        if k[0] < 0:
            continue
        groups.setdefault(k[1:], []).append(int(e.hbm_bytes()))
    total = 0
    for sizes in groups.values():
        if len(sizes) > 1:
            total += sum(sizes) - max(sizes)
    return total


def _entry_dev_bytes(key, ent) -> Dict[int, int]:
    """device index → physical bytes one cache entry holds there. Local
    entries charge their device wholesale; pod-partitioned entries walk
    their slabs and charge each owner device what it actually holds."""
    d = key[0]
    owners = getattr(ent, "owners", None)
    if d >= 0 or not owners:
        return {d if d >= 0 else 0: int(ent.hbm_bytes())}
    out: Dict[int, int] = {}
    for s, a in ent._arrays():
        o = owners[s] if s < len(owners) else owners[-1]
        out[o] = out.get(o, 0) + int(a.nbytes)
    return out or {0: 0}


def evict_device(dead: int, survivors=None) -> int:
    """Tear down a quarantined pool member's cache shard (the health
    monitor calls this when a device is lost). Per-device entries keyed
    to `dead` are evicted wholesale — small-table replicas lazily
    re-replicate on survivors on next touch. Pod-partitioned (dev == -1)
    entries lose ONLY the slabs the dead device owned: those device
    tuples are nulled (best-effort `jax.Array.delete()` on arrays no
    surviving slab shares), the holes/lost ledgers grow, and each lost
    contiguous owner run is re-owned by the least-loaded survivor so the
    next statement re-encodes and re-uploads JUST those slabs — the
    untouched owners keep their arrays. Delta generations with lost
    slabs drop whole (the decline-to-rebuild ladder: their delta slab
    and tombstone state are pinned to owner geometry). Aligned join
    structures live on device 0 and drop when device 0 dies.

    → number of cache entries touched."""
    dead = int(dead)
    surv = [int(s) for s in (survivors or []) if int(s) != dead]
    dead_c, dead_a, rehomed = [], [], []
    with LOCK:
        for k in [k for k in CACHE if k[0] == dead]:
            ent = CACHE.pop(k, None)
            if ent is not None:
                dead_c.append((k, ent))
        if dead == 0 and _ALIGNED:
            dead_a.extend(_ALIGNED.values())
            _ALIGNED.clear()
        prot = _all_protected()
        for k in [k for k in CACHE if k[0] < 0]:
            ent = CACHE[k]
            owners = getattr(ent, "owners", None)
            if not owners or dead not in owners:
                continue
            if getattr(ent, "is_delta", False) or not surv:
                CACHE.pop(k, None)
                dead_c.append((k, ent))
                continue
            lost = [s for s, o in enumerate(owners) if o == dead]
            doomed = []
            for i, slabs in ent.dev.items():
                for s in lost:
                    if s < len(slabs) and slabs[s] is not None:
                        doomed.append(slabs[s])
                        slabs[s] = None
                    ent.holes[i] = ent.holes.get(i, frozenset()) \
                        | frozenset([s])
                    ent.lost.add(s)
            # re-own each contiguous lost run onto the least-loaded
            # survivor (ties break low) — keeps owner spans contiguous
            run = []
            for s in lost + [None]:
                if run and (s is None or s != run[-1] + 1):
                    load = {d: 0 for d in surv}
                    for o in owners:
                        if o in load:
                            load[o] += 1
                    tgt = min(surv, key=lambda d: (load[d], d))
                    for r in run:
                        owners[r] = tgt
                    run = []
                if s is not None:
                    run.append(s)
            # best-effort delete of arrays no surviving slab shares
            # (dict-layout dictvals ride every slab a device owns) —
            # deferred to refcounting when a statement is mid-compute
            if doomed and k[1:3] not in prot:
                keep = set()
                for slabs in ent.dev.values():
                    for t in slabs:
                        if t is not None:
                            keep.update(id(a) for a in t)
                seen = set()
                for t in doomed:
                    for a in t:
                        if id(a) in keep or id(a) in seen:
                            continue
                        seen.add(id(a))
                        _delete_array(a)
            rehomed.append(k)
    for k, ent in dead_c:
        safe_delete(ent, k[1:3])
    for ent in dead_a:
        safe_delete(ent)
    if timeline.ENABLED and (dead_c or rehomed):
        timeline.instant(f"device-evict dev{dead}", "cache",
                         args={"dropped": len(dead_c),
                               "rehomed": len(rehomed)})
    return len(dead_c) + len(rehomed)


def collect_parts(ctx, scan, coverage: bool = False):
    """Materialize the scan's region stream host-side (no column copies:
    alignment reuses region arrays; only partially-deleted regions filter).

    With `coverage`, also return the region-level ledger a later delta
    extension diffs against: per enumerated region its (id, row count,
    alive mask, live-row base offset) — regions are immutable (every
    write builds new Region objects), so holding the build-time alive
    masks is safe — plus the max region id across the WHOLE TableData
    (a region that later re-enters partition scope via the part-reset on
    delete must force a rebuild, and only an id ceiling can tell it
    apart from a genuinely appended region)."""
    parts = []
    cov = []
    total = 0
    pruned = getattr(scan, "partitions", None)
    for region, chunk, alive in ctx.scan_table(
            scan.table.id, None if pruned is None else set(pruned)):
        chunk = align_chunk_to_schema(chunk, scan.table)
        mask = None if alive.all() else alive
        n = chunk.num_rows if mask is None else int(mask.sum())
        if coverage and region is not None:
            cov.append((region.id, region.num_rows,
                        None if mask is None else np.asarray(alive),
                        total, region))
        if n:
            parts.append((chunk, mask))
            total += n
    if not coverage:
        return parts, total
    td = ctx.snapshot.table_data(scan.table.id) \
        if getattr(ctx, "txn", None) is None else None
    max_rid = max((r.id for r in td.regions), default=-1) \
        if td is not None else -1
    return parts, total, cov, max_rid


def materialize_col(ent: CachedTable, col_idx: int):
    vals_list, valid_list = [], []
    for chunk, mask in ent.parts:
        col = chunk.columns[col_idx]
        v = col.values
        m = col.valid_mask()
        if mask is not None:
            v = v[mask]
            m = m[mask]
        vals_list.append(v)
        valid_list.append(m)
    if len(vals_list) == 1:
        return vals_list[0], valid_list[0]
    return np.concatenate(vals_list), np.concatenate(valid_list)


def encode_col(ftype, vals: np.ndarray, valid: np.ndarray):
    """→ (device-ready values, dictionary or None). Strings become sorted-
    dictionary rank codes (order-preserving, so comparisons work on codes);
    DOUBLE narrows to the device float dtype."""
    from tidb_tpu.ops.jax_env import device_float_dtype
    if ftype.is_varlen:
        return encode_strings(Column(ftype, vals, None))
    if vals.dtype == np.dtype(np.float64):
        vals = vals.astype(np.dtype(device_float_dtype()))
    return vals, None


def col_bounds(vals: np.ndarray, valid: np.ndarray,
                dictionary) -> Optional[Tuple[int, int]]:
    if dictionary is not None:
        return (0, len(dictionary) - 1) if len(dictionary) else None
    if vals.dtype.kind not in "iu":
        return None
    vv = vals if valid.all() else vals[valid]
    if not len(vv):
        return None
    return int(vv.min()), int(vv.max())


#: rows of strings one numpy call sorts or searches. Comparing objects,
#: numpy keeps the interpreter's lock for the whole call: in pieces, the
#: other threads run between two calls.
STR_CHUNK = 1 << 16
#: the same for a rebuild BESIDE the statements (a compaction's), and the
#: nap after each piece. A statement takes the interpreter's lock back
#: some thousand times (every launch, wait, packet and region of a scan
#: gives it away) and waits each time for the piece in progress: beside
#: one `np.unique` over a column of 24M strings a statement stood still
#: for as long as it took (38 s, chip, SF=4), beside pieces of 65,536 rows
#: (≈ 20 ms each) two operations in a row still took 14 s where the
#: median is 0.33. A piece of 1,024 rows is done in a fraction of a
#: millisecond, and the nap hands the lock to whoever waits for it (a
#: bare release is won back by the releasing thread before the waiter
#: wakes). First touch, which a statement waits for anyway, does not nap.
BESIDE_CHUNK = 1 << 10
BESIDE_NAP = 5e-5
_BESIDE = threading.local()


@contextmanager
def beside_statements():
    """Host work of this thread that no statement waits for: it gives
    way to the threads that serve one."""
    _BESIDE.on = True
    try:
        yield
    finally:
        _BESIDE.on = False


def _str_pieces(n: int):
    beside = getattr(_BESIDE, "on", False)
    step = BESIDE_CHUNK if beside else STR_CHUNK
    for a in range(0, n, step):
        yield a, a + step
        if beside:
            time.sleep(BESIDE_NAP)


def _str_unique(vals: np.ndarray) -> np.ndarray:
    """`np.unique(vals)` of an object array of strings, piecewise."""
    found = [np.unique(vals[a:b]) for a, b in _str_pieces(vals.shape[0])]
    return np.unique(np.concatenate(found)) if len(found) > 1 \
        else np.unique(vals)


def _str_codes(keys: np.ndarray, vals: np.ndarray) -> np.ndarray:
    """`np.searchsorted(keys, vals)` as int32 codes, piecewise."""
    out = np.empty(vals.shape[0], dtype=np.int32)
    for a, b in _str_pieces(vals.shape[0]):
        out[a:b] = np.searchsorted(keys, vals[a:b])
    return out


def col_prep(ent: CachedTable, col_idx: int, ftype) -> dict:
    """Once-per-column host prep for the streamed first-touch: materialize
    the column and build the GLOBAL dictionary/bounds. Per-slab encoding
    then reduces to a searchsorted against the sorted keys (strings), an
    astype (DOUBLE) or a limb split (wide decimals) of the slab's slice —
    byte-identical to encoding the whole column at once, because the
    dictionary is global and searchsorted on the sorted unique keys IS
    np.unique's return_inverse."""
    with first_touch("materialize", col=col_idx):
        vals, valid = materialize_col(ent, col_idx)
    if ftype.is_wide_decimal:
        return {"kind": "wide", "vals": vals, "valid": valid,
                "n_limbs": ftype.wide_limb_count,
                "dict": None, "bounds": None, "layout": None}
    if ftype.is_varlen:
        with first_touch("dict", col=col_idx):
            str_vals = np.empty(vals.shape[0], dtype=object)
            for a, b in _str_pieces(vals.shape[0]):
                str_vals[a:b] = [str(v) for v in vals[a:b]]
            if ftype.is_ci:
                folded = fold_ci_array(str_vals)
                keys, first = np.unique(folded, return_index=True)
                dictionary = str_vals[first]    # representative per class
                prep = {"kind": "str", "vals": folded, "valid": valid,
                        "keys": keys}
            else:
                dictionary = _str_unique(str_vals)
                prep = {"kind": "str", "vals": str_vals, "valid": valid,
                        "keys": dictionary}
        prep["dict"] = dictionary
        prep["bounds"] = (0, len(dictionary) - 1) if len(dictionary) else None
        prep["layout"] = None
        if ent.compressed:
            # string columns already carry global dictionary codes
            # (int32, 0..card-1) — bit-pack the CODES at the observed
            # width (FoR with ref 0; a second dict layer would be noise)
            card = len(dictionary)
            pw = compress._round_width(max(card - 1, 0).bit_length())
            if pw is not None and pw <= 16:
                prep["layout"] = compress.ColLayout("pack", pw, 0, "int32")
        return prep
    if vals.dtype == np.dtype(np.float64):
        from tidb_tpu.ops.jax_env import device_float_dtype
        return {"kind": "float", "vals": vals, "valid": valid,
                "dtype": np.dtype(device_float_dtype()),
                "dict": None, "bounds": None, "layout": None}
    with first_touch("layout", col=col_idx):
        bounds = col_bounds(vals, valid, None)
    prep = {"kind": "num", "vals": vals, "valid": valid,
            "dict": None, "bounds": bounds, "layout": None}
    if ent.compressed:
        layout, dictvals = compress.choose_layout(vals, valid,
                                                  hints=workload_hints())
        prep["layout"] = layout
        prep["dictvals"] = dictvals
    return prep


def workload_hints() -> Optional[dict]:
    """Distill the Registry's per-digest statement profiles into layout
    hints for compress.choose_layout — the workload-adaptive half of
    the encoder. The one robust signal the profiles carry about the
    read side is result cardinality: a device workload that returns few
    rows per execution is dominated by aggregation/selective scans, so
    dictionary layouts earn their keep (dict codes feed group
    factorization directly) and the cardinality cap loosens."""
    try:
        profs = REGISTRY.summary_profiles()
    except Exception:  # noqa: BLE001 — hints are advisory, never fatal
        return None
    dev = [p for p in profs
           if p.get("engine") == "device" and p.get("count")]
    if not dev:
        return None
    calls = sum(p["count"] for p in dev)
    rows = sum(p["rows"] for p in dev)
    return {"group_heavy": rows <= 1024 * calls}


def col_zone_stats(ent: CachedTable, prep: dict):
    """Per-slab zone map for one prepped column, in the space the
    pruner compares in (see executor/zonemap.py). Wide decimals carry
    none — their limb planes have no totally-ordered slab stats."""
    k = prep["kind"]
    if k == "wide":
        return None
    if k == "str":
        codes = _str_codes(prep["keys"], prep["vals"])
        return zonemap.column_stats(codes, prep["valid"], ent.slab_cap,
                                    ent.base_total, "code")
    kind = "float" if k == "float" else "num"
    return zonemap.column_stats(prep["vals"], prep["valid"],
                                ent.slab_cap, ent.base_total, kind)


def _est_slab_phys(prep: dict, slab_cap: int) -> int:
    """Physical bytes ONE slab of a prepped column would upload —
    computable without encoding it (the h2d_skipped ledger for slabs
    that never encode)."""
    lay = prep.get("layout")
    if lay is not None:
        return compress.packed_slab_bytes(lay, slab_cap)
    k = prep["kind"]
    if k == "wide":
        return prep["n_limbs"] * slab_cap * 8 + slab_cap
    if k == "float":
        return slab_cap * np.dtype(prep["dtype"]).itemsize + slab_cap
    if k == "str":
        return slab_cap * 4 + slab_cap
    return slab_cap * prep["vals"].dtype.itemsize + slab_cap


def _slab_logical_est(ent: CachedTable, i: int, preps=None) -> int:
    """Logical (raw-equivalent) bytes ONE slab of column `i` answers
    for — resolvable even when the device tuple is a pruned hole."""
    lay = ent.layouts.get(i)
    if lay is not None:
        return compress.raw_slab_bytes(lay, ent.slab_cap)
    if preps and i in preps:
        # raw layout: physical == logical
        return _est_slab_phys(preps[i], ent.slab_cap)
    col = ent.dev.get(i)
    if col is None:
        return 0
    return max((col.slab_nbytes(s) for s in range(col.n_base)), default=0)


def _slab_host(prep: dict, start: int, stop: int, slab_cap: int):
    """Encode + pad ONE slab of a prepped column → (host vals, host mask)."""
    n = stop - start
    valid = prep["valid"][start:stop]
    kind = prep["kind"]
    if kind == "wide":
        v = compress.wide_decimal_limbs(prep["vals"][start:stop],
                                        prep["n_limbs"])
        if n < slab_cap:
            pv = np.zeros((v.shape[0], slab_cap), dtype=np.int64)
            pv[:, :n] = v
            v = pv
    else:
        if kind == "str":
            v = _str_codes(prep["keys"], prep["vals"][start:stop])
        elif kind == "float":
            v = prep["vals"][start:stop].astype(prep["dtype"])
        else:
            v = prep["vals"][start:stop]
        if n < slab_cap:
            pv = np.zeros(slab_cap, dtype=v.dtype)
            pv[:n] = v
            v = pv
    m = valid
    if n < slab_cap:
        pm = np.zeros(slab_cap, dtype=bool)
        pm[:n] = m
        m = pm
    layout = prep.get("layout")
    if layout is not None:
        return compress.pack_slab(layout, v, m, prep.get("dictvals"))
    return v, m


def _tuple_nbytes(t) -> int:
    """Physical bytes of one slab tuple (raw or packed). From shape and
    dtype: a device array's own `nbytes` costs the host several times a
    numpy array's, and a warm `open_table` asks once a resident array."""
    return sum(math.prod(a.shape) * a.dtype.itemsize for a in t)


def _logical_tuple_bytes(ent: CachedTable, i: int, t) -> int:
    """Logical (uncompressed-equivalent) bytes of one slab tuple."""
    lay = ent.layouts.get(i)
    if lay is None:
        return _tuple_nbytes(t)
    return compress.raw_slab_bytes(lay, ent.slab_cap)


def _note_storage_metrics(ent: CachedTable, key) -> None:
    if key is None:
        return
    REGISTRY.observe("tidb_tpu_table_physical_bytes",
                     float(ent.hbm_bytes()), {"table": str(key[2])})
    REGISTRY.observe("tidb_tpu_table_logical_bytes",
                     float(ent.logical_bytes()), {"table": str(key[2])})


def stream_slabs(ctx, ent: CachedTable, key, used_cols, preps, phases,
                  skip=frozenset(), fill=None, tail=None):
    """Generator behind open_table: per slab, encode the missing columns
    (host), issue their uploads (async device_put), and yield
    (slab_idx, {col: slab tuple}) covering EVERY used column so the
    caller can dispatch that slab's compute before the next encode —
    encode(k+1) ∥ upload(k) ∥ compute(k-1). Compressed columns encode to
    packed (words, mask_words[, dictvals]) tuples — only the PHYSICAL
    bytes cross PCIe; the PhaseTimer is charged both counts. Completed
    columns commit to the cache entry only after the LAST slab: a stream
    abandoned by an error or a CPU fallback never leaves a half-uploaded
    column behind.

    Slabs in `skip` were zone-map-pruned for the opening statement:
    they are never encoded, never uploaded, and never yielded — the
    committed column carries None holes there (ent.holes records them,
    so later statements with weaker predicates re-stream the column in
    full).

    `fill` (col → slab index set) marks columns already resident whose
    LOST slabs (nulled by evict_device when their owner was
    quarantined) are being re-homed: only those slabs encode and upload
    (to the slab's NEW owner — evict_device already re-owned the
    range), warm slabs reuse the live tuples, and the commit splices
    the refilled slabs into the existing column instead of replacing
    it.

    A delta generation streams its BASE slabs (no resident row ever
    moved, so the build's parts still say what they hold); `tail` (col →
    slab tuple) is the delta slab of each streamed column, appended at
    the commit."""
    new_slabs = {i: [] for i in preps}
    dev_idx = getattr(ent, "device", 0)
    owners = getattr(ent, "owners", None)

    def _put(a, d):
        # commit to the owning pool device when one is pinned; the
        # single-device fallback keeps the uncommitted jnp.asarray path.
        # A transfer failure at this boundary is a DEVICE fault, not a
        # statement fault: classify it typed so the health monitor can
        # quarantine the member and retry the statement on a survivor
        # (the abandoned stream is safe — columns only commit after the
        # last slab, first-commit-wins)
        try:
            failpoint.inject("device-lost-upload")
        except DeviceLost:
            raise
        except Exception as e:  # noqa: BLE001 — armed fault, classify
            raise DeviceLost(f"device upload failed: {e}",
                             device=d) from e
        h = device_handle(d)
        if h is None:
            return jnp.asarray(a)
        try:
            return jax.device_put(np.asarray(a), h)
        except Exception as e:  # noqa: BLE001 — transfer fault, classify
            raise DeviceLost(f"device upload failed: {e}",
                             device=d) from e

    # dict-layout columns upload their dictionary values ONCE PER OWNER
    # DEVICE (pod entries span several); the same device array rides
    # every slab tuple that device owns (deduped by identity in
    # hbm_bytes/delete). Raw encode has no dictionary → logical 0.
    dict_cols = frozenset(
        i for i, p in preps.items()
        if p.get("layout") is not None and p["layout"].kind == "dict")
    dict_dev = {}

    def _dict_for(i, d):
        # called under the upload phase (first slab that device owns)
        t = dict_dev.get((i, d))
        if t is None and fill is not None and i in fill:
            # partial refill: a survivor that already owns warm slabs of
            # this column holds the dictionary — reuse it, don't re-ship
            for s2, tup in enumerate(ent.dev.get(i, ())):
                if tup is not None and len(tup) >= 3 \
                        and owners is not None and s2 < len(owners) \
                        and owners[s2] == d:
                    t = tup[-1]
                    dict_dev[(i, d)] = t
                    break
        if t is None:
            t = _put(preps[i]["dictvals"], d)
            dict_dev[(i, d)] = t
            phases.add_h2d(int(t.nbytes), logical=0)
        return t

    for s in range(ent.base_slabs):
        if s in skip:
            # pruned cold slab: no encode, no PCIe, no dispatch — the
            # statement still answered for its rows, so the logical
            # scan ledger (effective-roofline numerator) is charged
            for i in new_slabs:
                new_slabs[i].append(None)
            zonemap.note_h2d_skipped(
                phases, sum(_est_slab_phys(p, ent.slab_cap)
                            for i, p in preps.items()
                            if fill is None or i not in fill),
                table=str(key[2]) if key is not None else "")
            phases.add_scan(0, logical=sum(_slab_logical_est(ent, i, preps)
                                           for i in used_cols))
            continue
        start = s * ent.slab_cap
        stop = min(start + ent.slab_cap, ent.base_total)
        host = {}
        with phases.phase("encode"):
            for i, prep in preps.items():
                if fill is not None and i in fill and s not in fill[i]:
                    continue    # warm slab of a partially-lost column
                with first_touch("pack", col=i, slab=s):
                    host[i] = _slab_host(prep, start, stop, ent.slab_cap)
        slab_dev = owners[s] if owners is not None and s < len(owners) \
            else dev_idx
        with phases.phase("upload"), first_touch("upload"):
            for i, ht in host.items():
                dev_t = tuple(_put(a, slab_dev) for a in ht)
                if i in dict_cols:
                    dev_t = dev_t + (_dict_for(i, slab_dev),)
                new_slabs[i].append(dev_t)
        for i in preps:
            if i not in host:
                # partial refill, warm slab: carry the live tuple so
                # the yielded cols dict and commit indexing line up
                new_slabs[i].append(ent.dev[i][s])
        phases.add_h2d(sum(_tuple_nbytes(ht) for ht in host.values()),
                       logical=sum(_logical_tuple_bytes(ent, i, ht)
                                   for i, ht in host.items()))
        phases.mark_in_flight()
        cols = {i: (new_slabs[i][s] if i in new_slabs else ent.dev[i][s])
                for i in used_cols}
        # HBM bytes this slab's compute will read — warm columns included,
        # so roofline scan_bytes covers the whole program, not just the
        # cold uploads
        phases.add_scan(sum(_tuple_nbytes(t) for t in cols.values()),
                        logical=sum(_logical_tuple_bytes(ent, i, t)
                                    for i, t in cols.items()))
        yield s, cols
    with LOCK:
        for i, slabs in new_slabs.items():
            if fill is not None and i in fill:
                # partial refill: splice ONLY the re-uploaded lost slabs
                # into the live column — untouched owners keep their
                # arrays; a raced identical refill loses harmlessly
                # (refcounting frees the loser's uploads)
                cur = ent.dev.get(i)
                if cur is None or cur.n_base != len(slabs):
                    continue
                for fs in fill[i]:
                    if cur[fs] is None:
                        cur[fs] = slabs[fs]
                rem = frozenset(h for h in ent.holes.get(i, frozenset())
                                if h < len(cur) and cur[h] is None)
                if rem:
                    ent.holes[i] = rem
                else:
                    ent.holes.pop(i, None)
                continue
            # first-commit-wins: two threads cold-loading the same column
            # concurrently both stream byte-identical slabs (the encode is
            # deterministic); the loser's arrays drop on the floor and
            # refcounting frees them — never a half-overwritten column
            if i not in ent.dev:
                ent.dev[i] = SlabColumn(
                    slabs, delta=tail[i] if tail and i in tail else None)
                if skip:
                    ent.holes[i] = frozenset(skip)
                else:
                    ent.holes.pop(i, None)
        if fill is not None and getattr(ent, "lost", None):
            # a lost slab heals once no resident column still holes it
            ent.lost = {ls for ls in ent.lost
                        if any(ls in ent.holes.get(i, frozenset())
                               for i in ent.dev)}
    phases.clear_in_flight()
    _note_storage_metrics(ent, key)
    if key is not None:
        budget = var_int(ctx.vars, "tidb_tpu_hbm_budget")
        _evict_to_budget(budget, keep=key, keep_tables=_protected(ctx))


def _validate_layouts(ent: CachedTable, used_cols) -> None:
    """Validate the layout descriptor of every column the statement is
    about to decode — on the serving path, BEFORE any program is built,
    so a corrupted descriptor surfaces as a typed LayoutError (warned CPU
    fallback in the executor) and never as silently wrong rows. The
    failpoint models the corruption: any armed value stands in for a
    descriptor that no longer matches the packed data."""
    corrupted = failpoint.inject("compressed-decode-mismatch")
    if corrupted is not None:
        raise LayoutError(
            f"compressed column layout descriptor corrupted "
            f"(failpoint: {corrupted!r}) — refusing to decode")
    for i in used_cols:
        lay = ent.layouts.get(i)
        if lay is not None:
            compress.validate(lay)


def decoded_slabs(ent: CachedTable, col: int):
    """Column slabs DECODED to raw (vals, valid) tuples — the one-off
    eager decode for aligned-join builds, whose outputs (midx/matched
    and gathered build columns) are cached raw in the fact slab layout,
    so the per-query tree/fused consumers of aligned columns never
    carry an in-trace decode. A delta slab is raw as it stands."""
    slabs = ent.dev[col]
    lay = ent.layouts.get(col)
    if lay is None:
        return slabs
    return [t if ent.slab_shape(s)[1]
            else compress.decode_slab(lay, t, ent.slab_cap, jnp)
            for s, t in enumerate(slabs)]


def storage_stats(store_id: Optional[int] = None) -> List[dict]:
    """Per-(table, column) physical/logical residency of every cached
    entry — the information_schema.table_storage source. Snapshot under
    the lock; byte math (which touches device array metadata only)
    happens outside it. `store_id` scopes the report to one store: a
    dead engine's entries linger until its store is collected, and
    table ids restart per engine, so an unscoped dump can attribute a
    stale entry to an unrelated live table."""
    _reap_dead_stores()
    with LOCK:
        entries = [(k, e) for k, e in CACHE.items()
                   if store_id is None or k[1] == store_id]
    rows = []
    for key, ent in entries:
        for i in sorted(ent.dev):
            lay = ent.layouts.get(i)
            seen = set()
            phys = 0
            # (with what the generations kept behind this one own of the
            # column: their delta slab's arrays)
            for g in (ent,) + tuple(getattr(ent, "kept", ())):
                col = g.dev.get(i)
                for _s, a in col.arrays() if col is not None else ():
                    if id(a) not in seen:
                        seen.add(id(a))
                        phys += a.nbytes
            zm = ent.zmaps.get(i)
            zlo = zhi = None
            if zm is not None:
                known_lo = [v for v in zm.lo if v is not None]
                known_hi = [v for v in zm.hi if v is not None]
                if known_lo:
                    zlo, zhi = min(known_lo), max(known_hi)
            rows.append({
                "table_id": key[2],
                "column": i,
                "layout": "raw" if lay is None else lay.sig(),
                "physical_bytes": int(phys),
                "logical_bytes": int(ent.logical_bytes(cols={i})),
                "zone_map_slabs": 0 if zm is None else zm.n_slabs,
                "zone_map_min": zlo,
                "zone_map_max": zhi,
                "zone_map_nulls": None if zm is None
                else int(sum(zm.nulls)),
            })
    return rows


def _protected(ctx) -> frozenset:
    """(store_id, table_id) pairs ANY in-flight statement still needs:
    the per-thread protect_tables registrations of every live thread,
    plus the legacy per-ExecContext attribute (kept for callers that set
    it directly) — so a mid-query budget eviction (which DELETES buffers)
    can't free a sibling statement's arrays."""
    own = getattr(ctx, "_device_cache_protect", frozenset())
    return frozenset(own) | _all_protected()


def open_table(ctx, scan, used_cols, max_slab: int, phases=None,
               prune: bool = False, delta_ok: bool = False,
               _plain: str = ""):
    """→ (CachedTable, slab stream or None) — the streamed first-touch.

    Warm path (every used column already resident) returns stream=None.
    Cold/partial first touch returns a generator yielding
    (slab_idx, {col: (vals, valid)}) per slab; driving per-slab compute
    between yields pipelines host encode behind device transfers. The
    column dictionaries and bounds ARE committed eagerly (program
    construction needs key bounds before the first slab runs); the device
    arrays commit only when the stream completes.

    Cacheable only for snapshot reads (ctx.txn is None); transaction reads
    build a transient entry so staged rows are visible without poisoning
    the shared cache.

    `prune=True` (the chain executor's streamed path) consults the
    zone maps: cold first touch streams ONLY the slabs the scan's
    conjuncts cannot prove empty (pruned slabs commit as None holes),
    and warm accounting charges physical scan bytes only for surviving
    slabs while still charging the full logical bytes the statement
    answered for. Callers that need complete columns (the tree/dist
    mega-slab paths, aligned builds) leave prune off — a column whose
    holes exceed the statement's prune set is re-streamed in full.

    `delta_ok`: the caller's slab programs take a delta generation as it
    is (liveness masks, a raw delta slab of its own capacity). Every other
    consumer assumes a live prefix and uniform slabs: where the table's
    entry is a delta generation it gets a plain rebuild, counted as a
    decline of gate `consumer` and cached BESIDE the generation (`_plain`,
    the gate's name: the same key with its partitions tagged "plain"),
    which stays where it is for the statements that extend it. A snapshot
    older than what the key keeps gets one the same way, gate `behind`,
    tagged "behind". The tagged slot of `consumer` never moves backwards
    either; the slot of `behind` holds the last such reader's snapshot.
    """
    table_id = scan.table.id
    tabs = getattr(phases, "tables", None)
    if tabs is not None:
        # the statement's table footprint — record_stmt folds it into
        # the digest profile, closing the loop locality placement
        # (scheduler.place_statement) routes by
        tabs.add(table_id)
    comp_on = var_on(ctx.vars, "tidb_tpu_compression")
    cacheable = getattr(ctx, "txn", None) is None
    td = ctx.snapshot.table_data(table_id) if cacheable else None
    # key by owning store too: distinct engines may reuse table ids; a
    # finalizer evicts a dead engine's entries so its HBM isn't pinned.
    # The leading element is the OWNING POOL DEVICE: each device keeps
    # its own lazily-built replica of small tables, while fact tables
    # past the partition threshold share ONE pod entry under dev == -1
    # whose slab ranges are spread across owner devices. Pod entries
    # only serve the pruning chain path — tree/dist/aligned callers
    # need complete local columns and keep per-device entries.
    store = getattr(ctx.snapshot, "store", None) if cacheable else None
    parts = getattr(scan, "partitions", None)
    dev = ctx_device(ctx) if cacheable else 0
    if cacheable and prune and td is not None and _pod_partition(ctx, td):
        dev = -1
    key = (dev, id(store), table_id,
           None if parts is None else tuple(parts)) if cacheable else None
    if _plain and key is not None:
        # (a slot a gate: a reader far behind never costs the order/filter
        # roots their plain table of the newest snapshot)
        key = key[:3] + (("plain" if _plain == "consumer" else _plain,
                          key[3]),)

    def _plain_beside(gate="consumer"):
        return open_table(ctx, scan, used_cols, max_slab, phases=phases,
                          prune=prune, _plain=gate)

    _reap_dead_stores()
    with LOCK:
        if store is not None and id(store) not in _STORE_FINALIZERS:
            import weakref
            _STORE_FINALIZERS[id(store)] = weakref.finalize(
                store, _DEAD_STORES.append, id(store))

    def _usable(e):
        # td identity = data freshness; n_cols = DDL (ADD/DROP COLUMN)
        # guard; compressed must match the session's tidb_tpu_compression
        # so toggling it rebuilds the entry (the A/B comparison knob)
        return (e.td is td and e.max_slab == max_slab
                and e.n_cols == len(scan.schema)
                and e.compressed == comp_on)

    stale = None
    newest = None           # the key's entry, where `td` is not its data
    age = "newest"
    pv = _previewing()
    version = int(getattr(ctx.snapshot, "version", 0) or 0) \
        if cacheable else 0
    with LOCK:
        ent = CACHE.get(key) if cacheable else None
        if pv is not None and key == pv.key:
            ent = pv.ent
            if not _usable(ent) or (ent.is_delta and not delta_ok):
                raise PreviewMiss(f"table {table_id}")
        elif ent is not None and not _usable(ent):
            if (ent.td is not None and td is not None
                    and ent.max_slab == max_slab
                    and ent.n_cols == len(scan.schema)
                    and ent.compressed == comp_on
                    and ent.seen is not None and not _plain):
                # not this snapshot's ONLY because the data moved on
                # (geometry, schema width and compression all still
                # match): the snapshot's own generation if it is kept,
                # else the incremental delta extension of the nearest
                # older one, before paying a rebuild
                newest, ent = ent, None
            elif _plain == "consumer" and ent.delta_version > version:
                # a later snapshot's plain table stays where it is (built
                # below: this statement's serves it alone)
                ent = None
            else:
                CACHE.pop(key, None)
                stale = ent
                ent = None
        elif ent is not None:
            CACHE.move_to_end(key)
    if stale is not None:
        safe_delete(stale, key[1:3])
    if newest is not None:
        from tidb_tpu.executor import delta as _delta
        with timeline.span("delta.generation", "delta", table=table_id):
            ent, base = _generation_for(newest, version, _usable)
            if ent is not None:
                age = "kept"
            forward = base is not None
            live = _store_holds(store, table_id)
            still_held = None if live is None else \
                (lambda g: id(g.td) in live)
            # one commit at a time: every extension then has the shapes of
            # ONE transaction, whatever the number of commits since the
            # cache was last read (no program of a new bucket size in a
            # statement)
            for step in _commits_to(ctx, store, table_id, base, td, version):
                ent, moved = None, []

                def _swap(new_ent):
                    # (under the extensions' lock: the next statement to
                    # get its turn finds this generation installed)
                    with LOCK:
                        cur = CACHE.get(key)
                        if cur is None or cur.lineage == new_ent.lineage:
                            # the generation swap: in-flight readers keep
                            # the object they hold (their snapshot), a
                            # statement at this snapshot or a later one
                            # finds this. No generation is deleted here —
                            # those of one base build share its device
                            # arrays; refcounting frees what a dropped one
                            # alone held.
                            moved[:] = _install_generation(
                                CACHE, key, cur, new_ent,
                                lambda g: g.td is new_ent.td,
                                lambda g: g.delta_version, _table_arrays,
                                still_held)
                        elif _usable(cur):
                            # (rebuilt meanwhile, at this snapshot)
                            moved[:] = cur, "newest"
                        else:
                            # the key went to another base build meanwhile
                            # (a rebuild, a compaction): served as it is
                            moved[:] = new_ent, "newest" \
                                if new_ent.delta_version \
                                > cur.delta_version else "kept"

                if _delta.extend_entry(
                        step, scan, base, max_slab, phases,
                        quiet=pv is not None, then=_swap,
                        made=lambda: _generation_of(
                            CACHE.get(key),
                            step.snapshot.table_data(table_id),
                            base.lineage)) is None:
                    break
                base = ent = moved[0]
                age = moved[1]
                if ent.td is td:
                    break
            if ent is None and pv is not None:
                # (a warm-up never costs the statements in flight an entry)
                raise PreviewMiss(f"table {table_id}")
            if ent is None and forward:
                # the extension of the newest generation declined (a gate
                # tripped): drop it and rebuild — but only delete it if
                # WE pop it: when another thread replaced the slot (its
                # own extension won), that entry may share the base
                # device arrays with `newest`, and an explicit delete
                # here would free buffers it is serving.
                dead = None
                with LOCK:
                    cur = CACHE.get(key)
                    if cur is newest:
                        CACHE.pop(key, None)
                        dead = newest
                    elif cur is not None and _usable(cur):
                        ent = cur
                if dead is not None:
                    safe_delete(dead, key[1:3])
            timeline.tag(age="rebuilt" if ent is None else age)
        if ent is None and not forward:
            # a snapshot older than what is kept: the counted plain rebuild
            # BESIDE the cache. The key keeps its newest generation.
            return _plain_beside("behind")
    if ent is not None and ent.is_delta and not delta_ok:
        # a delta generation (just extended, or fresh), and a consumer
        # that cannot take one
        return _plain_beside()
    if ent is None:
        if _plain:
            from tidb_tpu.executor import delta as _delta
            _delta.decline(_plain, table_id)
            age = "rebuilt"
        if cacheable:
            parts, total, cov, max_rid = collect_parts(ctx, scan,
                                                        coverage=True)
        else:
            parts, total = collect_parts(ctx, scan)
            cov, max_rid = None, -1
        slab_cap = pow2(min(total, max_slab), lo=1024) if total else 1024
        n_slabs = (total + slab_cap - 1) // slab_cap
        built = CachedTable(td, max_slab, total, slab_cap, n_slabs, parts,
                            len(scan.schema), compressed=comp_on)
        built.device = dev
        if dev < 0:
            nd = max(scheduler.pool_devices(ctx), 1)
            # contiguous slab spans per owner: slab s → owner device
            # s*nd//n_slabs (monotone, covers every device when
            # n_slabs >= nd)
            built.owners = [min(s * nd // max(n_slabs, 1), nd - 1)
                            for s in range(n_slabs)]
        built.set_coverage(cov, max_rid)
        built.delta_version = int(getattr(ctx.snapshot, "version", 0) or 0) \
            if cacheable else 0
        if cacheable:
            victims = []
            replica = False
            with LOCK:
                cur = CACHE.get(key)
                if cur is not None and _usable(cur):
                    # lost a cold-build race: adopt the winner, drop ours
                    ent = cur
                    CACHE.move_to_end(key)
                elif cur is not None and _plain != "behind" \
                        and cur.delta_version > built.delta_version:
                    # a statement at a later snapshot got there meanwhile:
                    # its generation stays (an entry never moves
                    # backwards) and this build serves its statement alone
                    ent = built
                else:
                    if cur is not None:
                        # another statement's generation for ANOTHER
                        # snapshot: it shares its base arrays with what
                        # that statement computes on, so while any other
                        # thread protects the table its last reference
                        # frees it, not we
                        CACHE.pop(key)
                        if not _protected_elsewhere(key[1:3]):
                            victims.append(cur)
                        cur = None
                    ent = CACHE[key] = built
                    # lazy replication: another device already holds this
                    # (store, table, parts) — this install is a replica
                    replica = dev >= 0 and any(
                        k != key and k[0] >= 0 and k[1:] == key[1:]
                        for k in CACHE)
                    prot = _all_protected()
                    same = [k for k in CACHE if k[0] == dev]
                    over = len(same) - MAX_CACHED_TABLES
                    for k in same:
                        if over <= 0:
                            break
                        # per-device LRU trim skips the new entry and any
                        # table a live statement protects (a device may
                        # transiently exceed its cap under concurrency)
                        if k != key and k[1:3] not in prot:
                            victims.append(CACHE.pop(k))
                            over -= 1
            for v in victims:
                _entry_delete(v)
            if replica:
                REGISTRY.inc("tidb_tpu_table_replicas_total",
                             {"device": str(dev)})
        else:
            ent = built
        if ent.is_delta and not delta_ok:
            return _plain_beside()      # (adopted from a lost race)

    if cacheable:
        _count_read(age)
    if not ent.total:
        return ent, None
    ph = phases if phases is not None else PhaseTimer()
    if ent.is_delta and ent.delta_rows:
        ph.note_delta_rows(ent.delta_rows, token=id(ent))
    skip = zonemap.prune_slabs(ent, scan) if prune else frozenset()
    missing = []
    refill = []
    for i in used_cols:
        if i in ent.dev and ent.holes.get(i, frozenset()) <= skip:
            continue
        missing.append(i)
        if i in ent.dev:
            refill.append(i)
    fill = {}
    if refill:
        lost = set(getattr(ent, "lost", None) or ())
        full = []
        for i in refill:
            need = frozenset(ent.holes.get(i, frozenset()) - skip)
            if lost and need and need <= lost:
                # every uncovered hole is a quarantine-lost slab whose
                # range was already re-owned onto survivors: refill JUST
                # those slabs, keep the untouched owners' arrays
                fill[i] = need
            else:
                full.append(i)
        if full:
            with LOCK:
                for i in full:
                    # this statement's predicates reach slabs an earlier,
                    # more selective statement pruned away on cold touch:
                    # drop the holey generation and re-stream the column
                    # in full (refcounting frees the old device buffers)
                    ent.dev.pop(i, None)
                    ent.holes.pop(i, None)
    if not missing:
        # fully warm: the program READS every surviving resident slab —
        # charge those HBM bytes to the statement so roofline accounting
        # holds on hot re-runs; pruned slabs charge logical bytes only
        # (the statement answered for their rows without streaming them
        # — the effective-roofline numerator)
        _validate_layouts(ent, used_cols)
        phys = 0
        logi = 0
        for i in used_cols:
            slabs = ent.dev[i]
            logi += ent.n_slabs * _slab_logical_est(ent, i)
            for s in range(min(ent.n_slabs, len(slabs))):
                if s not in skip:
                    phys += slabs.slab_nbytes(s)     # (0 for a hole)
        ph.add_scan(phys, logical=logi)
        return ent, None
    failpoint.inject("device-transfer")
    ftypes = scan.schema.field_types
    preps = {}
    with ph.phase("encode"):
        for i in missing:
            preps[i] = col_prep(ent, i, ftypes[i])
            if i in fill:
                # the re-prep must reproduce the committed layout for
                # spliced slabs to decode alongside the warm ones — the
                # host data is unchanged (same td) so it does, unless
                # workload hints moved choose_layout: then demote to a
                # full re-stream of the column
                old = ent.layouts.get(i)
                new = preps[i]["layout"]
                same = (old is None and new is None) or (
                    old is not None and new is not None
                    and old.sig() == new.sig())
                if not same:
                    del fill[i]
                    with LOCK:
                        ent.dev.pop(i, None)
                        ent.holes.pop(i, None)
            ent.dicts[i] = preps[i]["dict"]
            ent.bounds[i] = preps[i]["bounds"]
            # layout commits eagerly with dicts/bounds: program
            # construction (signatures, decode emission) needs it before
            # the first slab streams; zone maps ride along so the prune
            # decision below already sees the new columns' statistics
            ent.layouts[i] = preps[i]["layout"]
            if ent.compressed:
                with first_touch("layout", col=i):
                    zm = col_zone_stats(ent, preps[i])
                if zm is not None:
                    ent.zmaps[i] = zm
    _validate_layouts(ent, used_cols)
    if prune:
        # re-consult with the freshly prepped columns' statistics — the
        # skip set only ever grows, so warm columns' holes stay covered
        skip = zonemap.prune_slabs(ent, scan)
    tail = None
    if ent.delta_cap:
        # the delta slab of the columns this generation did not hold yet
        from tidb_tpu.executor import delta as _delta
        tail = {}
        try:
            for i in missing:
                if i not in fill:
                    tail[i], nbytes = _delta.delta_column(ent, scan, i,
                                                          ftypes[i])
                    ph.add_h2d(nbytes, logical=nbytes)
        except _delta.Declined as d:
            # (an appended string the column's dictionary, built from the
            # base's rows, does not hold): rebuild fresh
            _delta.decline(d.gate, table_id)
            if pv is not None:
                raise PreviewMiss(f"table {table_id}") from d
            _drop_entry(key, ent)
            return open_table(ctx, scan, used_cols, max_slab, phases=phases,
                              prune=prune, delta_ok=delta_ok, _plain=_plain)
    return ent, stream_slabs(ctx, ent, key, list(used_cols), preps, ph,
                              skip=skip, fill=fill or None, tail=tail)


def get_table(ctx, scan, used_cols, max_slab: int,
              phases=None, delta_ok: bool = False) -> CachedTable:
    """→ CachedTable with every column in `used_cols` uploaded (open_table
    drained — callers that can't interleave compute, e.g. the join-tree
    path, still get the per-slab encode∥upload pipelining)."""
    ent, stream = open_table(ctx, scan, used_cols, max_slab, phases=phases,
                             delta_ok=delta_ok)
    if stream is not None:
        for _ in stream:
            pass
    return ent


def _evict_to_budget(budget: int, keep, keep_aligned=frozenset(),
                     keep_tables=frozenset()) -> None:
    """Drop LRU cached entries until each DEVICE's resident bytes fit
    the HBM budget (the budget is per device — eight pool members have
    eight HBMs), never the entries in active use (the caller's keeps
    PLUS every live thread's protect_tables registration). Aligned join
    structures evict first — derived data, rebuildable from the tables;
    they live on the default device, so they relieve device 0. Pod-
    partitioned entries charge each owner device only the slabs it
    actually holds."""
    dead_c, dead_a = [], []
    _reap_dead_stores()
    with LOCK:
        keep_tables = frozenset(keep_tables) | _all_protected()
        usage: Dict[int, int] = {}
        for k, e in CACHE.items():
            for d, b in _entry_dev_bytes(k, e).items():
                usage[d] = usage.get(d, 0) + b
        for e in _ALIGNED.values():
            usage[0] = usage.get(0, 0) + e.hbm_bytes()
        while usage.get(0, 0) > budget:
            victim = next((k for k in _ALIGNED if k not in keep_aligned),
                          None)
            if victim is None:
                break
            ent = _ALIGNED.pop(victim)
            usage[0] -= ent.hbm_bytes()
            dead_a.append(ent)
        while len(CACHE) > 1:
            over = {d for d, b in usage.items() if b > budget}
            if not over:
                break
            # keep_tables holds (store_id, table_id) pairs; cache keys
            # carry device and partition elements too — match on the
            # middle slice, else partitioned entries of a protected
            # table get evicted mid-query. LRU order: first matching
            # entry that relieves an over-budget device.
            victim = next(
                (k for k in CACHE
                 if k != keep and k[1:3] not in keep_tables
                 and set(_entry_dev_bytes(k, CACHE[k])) & over), None)
            if victim is None:
                break
            ent = CACHE.pop(victim)
            for d, b in _entry_dev_bytes(victim, ent).items():
                usage[d] = usage.get(d, 0) - b
            dead_c.append(ent)
    for ent in dead_c:
        _entry_delete(ent)
    for ent in dead_a:
        safe_delete(ent)


def aligned_budget_check(ctx, keep_keys=frozenset(),
                         keep_tables=frozenset()) -> None:
    """Enforce the HBM budget after aligned planning, never evicting the
    entries the in-flight query is about to execute with."""
    budget = var_int(ctx.vars, "tidb_tpu_hbm_budget")
    _evict_to_budget(budget, keep=None,
                     keep_aligned=frozenset(keep_keys),
                     keep_tables=frozenset(keep_tables))


# ---------------------------------------------------------------------------
# FK-aligned join cache (the join-index / coprocessor-cache analog)
# ---------------------------------------------------------------------------
#
# PK-FK equi joins dominate analytical plans (every TPC-H join), and on TPU
# the per-query cost of a hash/LUT join is NOT the build (one scatter) but
# the probe-side gathers: a random gather over tens of millions of rows is
# latency-bound (~9ns/row — 30x slower than streaming ops), and every build
# column gathered pays it again, every query.
#
# The TPU-native answer: gather ONCE, cache the result. For a join whose
# build side is unique on the key (verified at build time, not assumed), the
# per-fact-row match is a pure function of (fact key column, build key
# column) — independent of the query's filters and projections. So we cache,
# in the fact table's slab layout:
#   midx     int32 per fact row — matching build row (or garbage if none)
#   matched  bool  per fact row — a live, NULL-free key match exists
#   cols     build column c gathered through midx, masked by matched
# Filters on the build side then evaluate per-query AGAINST the aligned
# columns (they are per-fact-row now), so one cached structure serves every
# filter/projection combination — exactly how the reference's coprocessor
# cache (store/copr/coprocessor_cache.go) serves filter-variant scans from
# one snapshot, and the classic bitmap-join-index idea done columnar.
#
# Chained joins compose: the probe key of a snowflake's second hop (Q5's
# o_custkey) is itself an aligned column of the first hop, so the second
# entry's key path nests the first's. Freshness: every entry records the
# TableData identity tokens of ALL tables on its path; any mismatch (or
# explicit invalidate) drops it.


class AlignedJoin:
    """Cached FK-aligned join structure for ONE (fact path, build) pair.

    What it holds a fact slab — the match mask, the matched build row, the
    gathered build columns — is a `SlabColumn` each, in the fact's row
    space: per-slab arrays as built, ONE array with a leading axis of base
    slabs once a statement program reads them (`SlabPicks.of`), the delta
    slab's arrays behind. `midx` is read by the builds and by the unmatch
    of dead build keys alone, which stacks it beside the match mask the
    first time it runs (`_advance_aligned`).

    A structure follows its tables through delta generations
    (`_advance_aligned`): no fact row ever moves, so what it holds for the
    base slabs stays; a build row that dies unmatches the fact rows that
    carry its key (a few key ranges compared against the fact key, no
    gather), a build row that arrives enters the lookup table, a fact
    row that arrives is probed and its build columns gathered — the new
    rows alone, into the arrays of the fact's delta slab."""

    __slots__ = ("tds", "slab_cap", "n_slabs", "unique", "matched",
                 "midx", "cols", "build_nb", "key", "lut", "lo", "domain",
                 "space", "dangling", "bcat", "version", "kept",
                 "kept_bytes")

    def __init__(self, key, tds, slab_cap, n_slabs, build_nb):
        self.key = key
        self.tds = tds              # table_id → TableData token
        self.slab_cap = slab_cap    # fact slab layout at build
        self.n_slabs = n_slabs
        self.build_nb = build_nb    # build-side padded row count
        self.unique = True
        self.matched = SlabColumn()     # per fact slab: bool (slab_cap,)
        self.midx = SlabColumn()        # per fact slab: int32 (slab_cap,)
        self.cols: Dict[int, SlabColumn] = {}   # build col → (v, m) slabs
        self.lut = None             # key - lo → build row, -1 where none
        self.lo, self.domain = 0, 0
        # the base builds whose row POSITIONS this structure holds: the
        # probe source's (the fact table's; a chained hop's, its parent
        # structure's) and last the build table's. A compaction moves
        # rows without changing the table's data, so freshness is the
        # data's identity AND these
        self.space: Tuple = ()
        # live fact rows whose key matches no live build row (a device
        # scalar, fetched only when a build row arrives: one that could
        # adopt such a row means a rebuild)
        self.dangling = None
        self.bcat: Dict[int, Tuple] = {}    # build col → decoded base rows
        # the store version of the snapshot whose generations it pairs,
        # and the older structures kept behind it while it is the key's
        # newest (as `CachedTable.kept`)
        self.version = 0
        self.kept: tuple = ()
        self.kept_bytes = 0

    def _owned(self):
        for col in (self.matched, self.midx, *self.cols.values()):
            for _s, a in col.arrays():
                yield a
        if self.lut is not None:
            yield self.lut
        for v, m in self.bcat.values():
            yield v
            yield m

    def hbm_bytes(self) -> int:
        return sum(a.nbytes for a in self._owned()) + self.kept_bytes

    def delete(self) -> None:
        """Free device buffers on eviction (see CachedTable.delete)."""
        for a in list(self._owned()):
            _delete_array(a)
        self.matched, self.midx, self.lut = SlabColumn(), SlabColumn(), None
        self.cols.clear()
        self.bcat.clear()


def _fresh(ctx, tds) -> bool:
    return all(ctx.snapshot.table_data(tid) is td for tid, td in tds.items())


def _build_cat(ent: CachedTable, col: int, base_only: bool = False):
    """Build-side column slabs concatenated (build tables are usually one
    slab; concat is a no-op then). Wide decimals concat on the row axis.
    Compressed slabs decode here — the LUT/gather builds below run once
    per cached structure, so the eager decode is off the per-query path.
    A delta generation's raw delta slab follows its base slabs."""
    slabs = decoded_slabs(ent, col)
    if base_only:
        slabs = slabs[:ent.base_slabs]
    if len(slabs) == 1:
        return slabs[0]
    return (jnp.concatenate([s[0] for s in slabs], axis=-1),
            jnp.concatenate([s[1] for s in slabs]))


def _alive_cat(ent: CachedTable):
    """Row liveness over `_build_cat`'s rows."""
    masks = [ent.slab_mask(s) for s in range(ent.n_slabs)]
    return masks[0] if len(masks) == 1 else jnp.concatenate(list(masks))


ALIGNED_DOMAIN_CAP = 1 << 26    # max build-key LUT size at cache build
ALIGNED_MAX_RANGES = 8          # dead build keys as key ranges, up to here


def get_aligned(ctx, key, tds: Dict[int, object], fact_slabs,
                build_ent: CachedTable, build_key_col: int,
                bounds: Tuple[int, int], slab_cap: int, n_slabs: int,
                space: Tuple, fact=None):
    """→ AlignedJoin for `key`, building midx/matched on first use, or None
    when the build side turns out non-unique on the key (the negative
    result is cached too — one LUT build per key, not one per query).

    key: hashable path signature (store id, probe-source path, build table,
    build key col). tds: table_id → TableData token for EVERY table on the
    path — freshness is identity of all of them.
    fact_slabs: () → (per-fact-slab probe key arrays, validity arrays),
    raw ints or dictionary codes already in the build's code space, asked
    for only when something has to be built. bounds: the build key
    column's (lo, hi) value domain. space: the lineages of the base builds
    the probe source's rows are positioned in (`AlignedJoin.space` less
    the build's). fact: (fact CachedTable, its key column) when the probe
    key is a column of the fact scan itself — such a structure follows
    both tables' delta generations."""
    from tidb_tpu.ops.jax_env import named_jit, program_name
    stale = None
    space = tuple(space) + (build_ent.lineage,)
    pv = _previewing()
    # (a compaction's warm-up builds aside: `Preview`)
    tbl = pv.aligned if pv is not None else _ALIGNED
    version = int(getattr(ctx.snapshot, "version", 0) or 0)

    def _family(g):
        # of these base builds, in this layout: its arrays are positioned
        # as this snapshot's generations are
        return g.space == space and g.slab_cap == slab_cap

    def _this(g):
        # the structure of THIS snapshot's generations of every table
        return _fresh(ctx, g.tds) and _family(g) and g.n_slabs == n_slabs

    def _install(new):
        """Under the key, before or behind what it holds → what serves."""
        new.version = version
        with LOCK:
            cur = tbl.get(key)
            if cur is None or (_family(cur) and cur.unique):
                new, _age = _install_generation(
                    tbl, key, cur, new, _this, lambda g: g.version,
                    AlignedJoin._owned)
            elif _this(cur):
                new = cur       # (lost a concurrent build race: adopt)
            elif cur.version <= version:
                tbl[key] = new
                tbl.move_to_end(key)
            # (else: another base build's, and newer: served uninstalled)
        return new

    with LOCK:
        ent = tbl.get(key)
        if ent is not None:
            # the structure that pairs the generations of this snapshot:
            # the key's newest, or one kept behind it for the statements
            # another connection's commit has overtaken
            for g in (ent,) + ent.kept[::-1]:
                if _this(g):
                    if g is ent:
                        tbl.move_to_end(key)
                    return g if g.unique else None
            stale = ent
    if stale is not None and fact is not None and stale.unique:
        with timeline.span("delta.aligned", "delta",
                           table=next(iter(tds), 0)):
            # from the youngest structure whose tables' generations these
            # ones were extended from (`steps`: this one's are not)
            for src in (stale,) + stale.kept[::-1]:
                new = _advance_aligned(src, tds, fact[0], fact[1],
                                       build_ent, build_key_col, bounds)
                if new != "steps":
                    break
        if isinstance(new, str):
            # the structure is rebuilt in full (a decode, a probe and a
            # gather over every fact row): counted like a table's rebuild
            if pv is None:
                from tidb_tpu.executor import delta as _delta
                _delta.decline("aligned-" + new, next(iter(tds), 0))
        else:
            return _install(new)
    if stale is not None and not (_family(stale) and stale.unique) \
            and stale.version <= version:
        # another base build's rows (a compaction, a rebuild): nothing of
        # it serves any snapshot's generations again. (One of a LATER
        # snapshot stays: this statement is behind the cache, on tables
        # rebuilt beside it, and what it builds serves it alone.)
        with LOCK:
            if tbl.get(key) is stale:
                tbl.pop(key, None)
        safe_delete(stale)

    lo, hi = bounds
    domain = hi - lo + 1
    if domain > ALIGNED_DOMAIN_CAP:
        return None
    bk_v, bk_m = _build_cat(build_ent, build_key_col)
    b_alive = _alive_cat(build_ent)
    nb = int(bk_v.shape[0])
    ent = AlignedJoin(key, tds, slab_cap, n_slabs, nb)
    ent.lo, ent.domain = lo, domain
    ent.space = space
    # named after what the trace bakes in, and nothing of this process
    # (`key` holds object ids) or of the data: the name is part of the
    # persistent cache's key, and a restarted server must find these
    # programs again
    sig = repr((lo, hi, nb))

    def _lut(bv, bm, alive_b):
        iota = jnp.arange(nb, dtype=jnp.int32)
        alive = jnp.asarray(bm) & alive_b
        code = jnp.where(alive, jnp.asarray(bv).astype(jnp.int64) - lo,
                         jnp.int64(domain))
        code = jnp.clip(code, 0, domain).astype(jnp.int32)
        cnt = jnp.zeros(domain + 1, jnp.int32).at[code].add(
            jnp.where(alive, 1, 0).astype(jnp.int32))
        lut = jnp.full(domain + 1, -1, jnp.int32).at[code].set(
            jnp.where(alive, iota, -1))
        return cnt[:domain].max() if domain else jnp.int32(0), lut

    maxcnt, lut = named_jit(_lut, program_name("gather_lut", sig))(
        bk_v, bk_m, b_alive)
    if int(jax.device_get(maxcnt)) > 1:
        ent.unique = False          # negative result cached
        with LOCK:
            if key not in tbl:
                tbl[key] = ent
        return None
    ent.lut = lut

    def _probe(lut_, pv, pm, alive_f):
        c = jnp.asarray(pv).astype(jnp.int64) - lo
        in_dom = (c >= 0) & (c <= (hi - lo))
        ci = jnp.clip(c, 0, domain - 1).astype(jnp.int32)
        midx = jnp.take(lut_, ci)
        matched = jnp.asarray(pm) & in_dom & (midx >= 0)
        return jnp.clip(midx, 0, nb - 1), matched, \
            jnp.sum(alive_f & jnp.asarray(pm) & ~matched, dtype=jnp.int32)

    _probe = named_jit(_probe, program_name("gather_probe", sig))
    codes, valids = fact_slabs()
    dangling = jnp.int32(0)
    midxs, matcheds = [], []
    for s, (pv, pm) in enumerate(zip(codes, valids)):
        # (a chained hop's fact rows are another structure's: all count)
        alive_f = fact[0].slab_mask(s) if fact is not None else \
            jnp.ones(int(pv.shape[-1]), dtype=bool)
        midx, matched, n_dang = _probe(lut, pv, pm, alive_f)
        midxs.append(midx)
        matcheds.append(matched)
        dangling = dangling + n_dang
    # (a delta generation's last slab is the raw delta slab's)
    n_base = fact[0].base_slabs if fact is not None else len(midxs)
    ent.midx = SlabColumn(midxs[:n_base], *midxs[n_base:])
    ent.matched = SlabColumn(matcheds[:n_base], *matcheds[n_base:])
    ent.dangling = dangling
    got = _install(ent)
    return got if got.unique else None


def _gather_program(col: int, bv, cap: int):

    def _gather(bv_, bm_, midx, matched):
        v = jnp.take(jnp.asarray(bv_), midx, axis=-1)
        m = jnp.take(jnp.asarray(bm_), midx) & matched
        return v, m

    # the build column is an ARGUMENT: no table data in the program, so
    # every data set of one shape shares one executable
    return device_emit.delta_program(
        "gather", (col, bv.shape, str(bv.dtype), cap), lambda: _gather)


def aligned_col(ent: AlignedJoin, build_ent: CachedTable, col: int):
    """Ensure build column `col` is materialized in the fact row space;
    → per-fact-slab [(v, m)] (wide decimals keep their limb-plane axis)."""
    cached = ent.cols.get(col)
    if cached is not None:
        return cached
    bv, bm = _build_cat(build_ent, col)
    slabs = [_gather_program(col, bv, int(midx.shape[0]))(
        bv, bm, midx, matched)
        for midx, matched in zip(ent.midx, ent.matched)]
    n_base = ent.matched.n_base
    slabs = SlabColumn(slabs[:n_base], *slabs[n_base:])
    with LOCK:
        # first-commit-wins against a concurrent identical gather
        return ent.cols.setdefault(col, slabs)


def _steps_since(ent: CachedTable, td_from):
    """The extension steps that led from `td_from` to this generation, or
    None when the chain is not (or no longer) in its memory."""
    if ent.td is td_from:
        return ()
    for k, st in enumerate(ent.steps):
        if st["from"] is td_from:
            chain = ent.steps[k:]
            ok = all(a["to"] is b["from"] for a, b in zip(chain, chain[1:]))
            return chain if ok and chain[-1]["to"] is ent.td else None
    return None


def _key_col(region, col: int, start: int, stop: int):
    if col >= region.chunk.num_cols:
        return None
    c = region.chunk.columns[col]
    return (np.asarray(c.values[start:stop]),
            np.asarray(c.valid_mask()[start:stop]))


def _advance_aligned(old: AlignedJoin, tds, fact_ent: CachedTable,
                     fact_col: int, build_ent: CachedTable, bcol: int,
                     bounds):
    """`old`, brought to the generations `fact_ent` and `build_ent` are
    at → a new AlignedJoin sharing what did not change, or, where only a
    rebuild is right, the reason as a word: `lineage` (another base
    build), `steps` (a chain of steps no longer remembered), `key-domain`
    (a key outside the lookup table), `schema`, `key-taken` (a build row
    arriving onto a key a live one holds), `dangling` (a build row
    arriving while live fact rows match none: one of them may be its —
    an UPDATE of a build row is a key that dies and arrives)."""
    from tidb_tpu.executor import delta
    fact_tid = next((t for t, td in tds.items() if td is fact_ent.td), None)
    build_tid = next((t for t, td in tds.items()
                      if td is build_ent.td and t != fact_tid), None)
    if old.space != (fact_ent.lineage, build_ent.lineage) \
            or fact_tid is None or build_tid is None \
            or set(old.tds) != {fact_tid, build_tid} \
            or fact_ent.slab_cap != old.slab_cap:
        return "lineage"
    fsteps = _steps_since(fact_ent, old.tds[fact_tid])
    bsteps = _steps_since(build_ent, old.tds[build_tid])
    if fsteps is None or bsteps is None:
        return "steps"
    lo, domain = old.lo, old.domain
    if bounds[0] < lo or bounds[1] > lo + domain - 1:
        return "key-domain"     # it outgrew the lookup table
    new = AlignedJoin(old.key, dict(tds), old.slab_cap, fact_ent.n_slabs,
                      old.build_nb)
    new.lo, new.domain, new.space = lo, domain, old.space
    new.lut, new.dangling = old.lut, old.dangling
    new.cols = {c: sl.fork() for c, sl in old.cols.items()}
    new.bcat = dict(old.bcat)
    base_n = build_ent.base_slabs * build_ent.slab_cap
    new.build_nb = base_n + build_ent.delta_cap
    pad = delta.pad_idx

    # ---- the build side, step by step: dead keys leave the lookup table
    # and unmatch the fact rows that carry them, new keys enter it
    dead_all = []
    arrived, taken_at = False, []
    for st in bsteps:
        dead_keys = []
        for now, before in st["dead"]:
            n = before.num_rows
            got = _key_col(now, bcol, 0, n)
            if got is None:
                return "schema"
            fresh = np.asarray(now.deleted[:n]) & ~before.deleted & got[1]
            dead_keys.append(got[0][fresh].astype(np.int64))
        keys, rows = [], []
        for region, start, stop, off in st["appended"]:
            got = _key_col(region, bcol, start, stop)
            if got is None:
                return "schema"
            live = got[1] & ~np.asarray(region.deleted[start:stop])
            keys.append(got[0][live].astype(np.int64))
            rows.append((base_n + off
                         + np.flatnonzero(live)).astype(np.int32))
        dk = np.concatenate(dead_keys) if dead_keys else \
            np.empty(0, np.int64)
        nk = np.concatenate(keys) if keys else np.empty(0, np.int64)
        if not dk.size and not nk.size:
            continue
        if nk.size and (nk.min() < lo or nk.max() > lo + domain - 1):
            return "key-domain"
        if np.unique(nk).size != nk.size:
            return "key-taken"
        arrived = arrived or bool(nk.size)
        dead_all.append(dk)
        clr = pad((dk - lo)[(dk >= lo) & (dk < lo + domain)], domain + 1)
        put = pad(nk - lo, domain + 1)
        val = np.full(put.shape, -1, dtype=np.int32)
        val[:nk.size] = np.concatenate(rows) if rows else val[:0]

        def _lut_step(lut, clr_, put_, val_):
            with jax.named_scope("delta_merge"):
                lut = lut.at[clr_].set(-1, mode="drop")
                taken = jnp.any(jnp.take(lut, put_, mode="fill",
                                         fill_value=-1) >= 0)
                return lut.at[put_].set(val_, mode="drop"), taken

        step = device_emit.delta_program(
            "delta_merge", ("lut", domain, clr.shape, put.shape),
            lambda: _lut_step)
        new.lut, taken = step(new.lut, clr, put, val)
        taken_at.append(taken)
    dk = np.unique(np.concatenate(dead_all)) if dead_all else \
        np.empty(0, np.int64)
    if dk.size:
        cut = np.flatnonzero(np.diff(dk) != 1)
        starts = np.concatenate([dk[:1], dk[cut + 1]])
        stops = np.concatenate([dk[cut], dk[-1:]])
        # a few runs of keys (a purge by key range) are compared with the
        # decoded key; scattered ones ask the lookup table whether the
        # key still leads to the row that was gathered from
        by_lut = starts.size > ALIGNED_MAX_RANGES
        ranges = np.full((ALIGNED_MAX_RANGES, 2), [1, 0], dtype=np.int64)
        if not by_lut:
            ranges[:starts.size, 0], ranges[:starts.size, 1] = starts, stops
        lay = fact_ent.layouts.get(fact_col)
        fcol, n_base = fact_ent.dev[fact_col], fact_ent.base_slabs
        # the base slabs of an entry that can hold them as ONE array are
        # read so, as the statement programs read them — stacked here if
        # no statement program came first: one program whose loop hands
        # out the new stacked mask, and no slab is sliced out. (Slabs on
        # several devices, or a lost one, keep their lists and the
        # program of one slab, which the raw delta slab runs anyway.)
        whole = n_base > 1 and fact_ent.owners is None \
            and not fact_ent.lost
        if whole:
            if fcol.holes():
                return "holes"
            picks = SlabPicks(fact_ent, range(n_base), fact_tid)
            masks = fact_ent.alive
            base = (picks.of(fcol), picks.of(old.matched),
                    picks.of(old.midx),
                    picks.of(delta.base_masks(fact_ent, fact_tid)
                             if masks is None else masks))
    # (what no advance rewrites is shared by identity; the match masks of
    # the base are written under a structure of this generation's own)
    new.matched, new.midx = old.matched.fork(own=True), old.midx.fork()
    if dk.size:
        for s in range(min(len(new.matched), fact_ent.n_slabs)):
            cap, raw = fact_ent.slab_shape(s)
            lay_s = None if raw else lay
            if whole and not raw and s:
                continue            # (slab 0's turn ran the whole base)

            def _unmatch(slab, matched, midx, alive, rg, lut,
                         lay_s=lay_s, cap=cap, by_lut=by_lut):
                with jax.named_scope("delta_merge"):
                    v, m = device_emit.emit_decode(lay_s, slab, cap) \
                        if lay_s is not None else slab[:2]
                    k = jnp.asarray(v).astype(jnp.int64)
                    if by_lut:
                        at = jnp.clip(k - lo, 0, domain - 1)
                        hit = jnp.take(lut, at.astype(jnp.int32)) != midx
                    else:
                        hit = jnp.zeros(cap, dtype=bool)
                        for r in range(ALIGNED_MAX_RANGES):
                            hit = hit | ((k >= rg[r, 0]) & (k <= rg[r, 1]))
                    hit = hit & matched
                    return matched & ~hit, \
                        jnp.sum(hit & alive, dtype=jnp.int32)

            key = ("unmatch", cap, None if lay_s is None else lay_s.sig(),
                   (lo, domain) if by_lut else None)
            if whole and not raw:
                def _unmatch_base(base, rows, rg, lut, _unmatch=_unmatch):
                    def turn(c, k):
                        matched, n_dang = _unmatch(
                            *in_place(base, rows, k), rg, lut)
                        # (as the stack holds a slab's mask: the loop
                        # hands out the new stack itself)
                        return c, (device_emit.fold(matched), n_dang)
                    _c, (matched, n_dang) = lax.scan(
                        turn, None, jnp.arange(n_base, dtype=jnp.int32))
                    return matched, jnp.sum(n_dang, dtype=jnp.int32)

                prog = device_emit.delta_program(
                    "delta_merge", key + (n_base,),
                    lambda _f=_unmatch_base: _f)
                matched, n_dang = prog(base, picks.vectors(), ranges,
                                       new.lut)
                new.matched.set_stack(matched)
            else:
                prog = device_emit.delta_program(
                    "delta_merge", key, lambda _unmatch=_unmatch: _unmatch)
                new.matched[s], n_dang = prog(
                    fcol[s], new.matched[s], new.midx[s],
                    fact_ent.slab_mask(s), ranges, new.lut)
            new.dangling = new.dangling + n_dang
    if arrived:
        # a build row may only arrive onto a key no live build row holds,
        # and when no live fact row waits for a key (its own, if the
        # key died in these very steps, dangles since the pass above)
        n_dang, taken = jax.device_get((new.dangling, taken_at))
        if any(taken):
            return "key-taken"
        if int(n_dang):
            return "dangling"

    # ---- the fact side: the rows that arrived, probed and gathered into
    # the arrays of the fact's delta slab
    if fact_ent.delta_cap and len(new.matched) == fact_ent.base_slabs:
        dcap = fact_ent.delta_cap
        new.matched.append(jnp.zeros(dcap, dtype=bool))
        new.midx.append(jnp.zeros(dcap, dtype=jnp.int32))
        for c, sl in new.cols.items():
            v_shape, v_dtype = sl.specs()[0]
            sl.append((jnp.zeros(v_shape[:-1] + (dcap,), dtype=v_dtype),
                       jnp.zeros(dcap, dtype=bool)))
    # (a step at a time: each has the shapes of ONE commit's rows, so the
    # program is the one every commit before it ran)
    for appended in (st["appended"] for st in fsteps if st["appended"]):
        dcap, d = fact_ent.delta_cap, fact_ent.base_slabs
        parts = [_key_col(r, fact_col, a, b) for r, a, b, _o in appended]
        if any(p is None for p in parts):
            return "schema"
        kv = np.concatenate([p[0] for p in parts]).astype(np.int64)
        km = np.concatenate([p[1] for p in parts])
        off, n = appended[0][3], int(kv.shape[0])
        bucket = pow2(n, delta.MIN_BUCKET)
        pk = np.zeros(bucket, dtype=np.int64)
        pk[:n] = kv
        pm = np.zeros(bucket, dtype=bool)
        pm[:n] = km
        cols = sorted(new.cols)
        for c in cols:
            if c not in new.bcat:
                new.bcat[c] = _build_cat(build_ent, c, base_only=True)
        bdelta = {c: build_ent.dev[c][build_ent.base_slabs][:2]
                  for c in cols} if build_ent.delta_cap else {}
        nb = new.build_nb

        def _extend(lut, keys, kmask, off_, n_, matched, midx, acols,
                    bcat, bdel, bucket=bucket, dcap=dcap, nb=nb):
            with jax.named_scope("delta_merge"):
                i = jnp.arange(bucket, dtype=jnp.int32)
                c = keys - lo
                ok = kmask & (c >= 0) & (c < domain) & (i < n_)
                mi = jnp.take(lut, jnp.clip(c, 0, domain - 1)
                              .astype(jnp.int32))
                hit = ok & (mi >= 0)
                mi = jnp.clip(mi, 0, nb - 1)
                at = jnp.where(i < n_, off_ + i, jnp.int32(dcap))
                out = {}
                for col, (av, am) in acols.items():
                    bv, bm = bcat[col]
                    in_base = mi < base_n
                    gv = jnp.take(bv, jnp.clip(mi, 0, base_n - 1), axis=-1)
                    gm = jnp.take(bm, jnp.clip(mi, 0, base_n - 1))
                    if col in bdel:
                        dv, dm = bdel[col]
                        di = jnp.clip(mi - base_n, 0, dv.shape[-1] - 1)
                        gv = jnp.where(in_base, gv,
                                       jnp.take(dv, di, axis=-1))
                        gm = jnp.where(in_base, gm, jnp.take(dm, di))
                    out[col] = (av.at[..., at].set(gv, mode="drop"),
                                am.at[at].set(gm & hit, mode="drop"))
                return (matched.at[at].set(hit, mode="drop"),
                        midx.at[at].set(mi, mode="drop"), out,
                        jnp.sum(kmask & (i < n_) & ~hit, dtype=jnp.int32))

        prog = device_emit.delta_program("delta_merge", (
            "aligned", lo, domain, bucket, dcap, base_n, nb, tuple(cols),
            bool(bdelta)), lambda _extend=_extend: _extend)
        with timeline.span("delta.upload", "delta",
                           bytes=pk.nbytes + pm.nbytes):
            new.matched[d], new.midx[d], out, n_dang = prog(
                new.lut, pk, pm, jnp.int32(off), jnp.int32(n),
                new.matched[d], new.midx[d],
                {c: new.cols[c][d] for c in cols}, new.bcat, bdelta)
        for c in cols:
            new.cols[c][d] = out[c]
        new.dangling = new.dangling + n_dang
    return new


