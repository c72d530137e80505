"""Segment (grouped) reduction primitives, numpy + jax backends.

The TPU-first reformulation of TiDB's hash aggregation (SURVEY §7 stage 4):
open-address hash tables have no efficient TPU form, so grouped reduction is
expressed as segment ops — rows combined into dense group slots. On numpy
these use `ufunc.at` (exact int64 — np.bincount would round through
float64). Under jit there are five lowerings, chosen from static shapes
alone; what each costs on a TPU v5e over one 8M-row slab (PERF.md §6):

* `flat` — ONE segment (an aggregate without GROUP BY): a plain reduction,
  no slot axis; ≈ 1 ms a state (PR 27).
* `masked` — `reduce(where(gid == iota, v, identity))`, one broadcast
  reduce per state; rows × slots of VPU work in emulated 64 bits: 0.26 ms a
  state at 12 slots, 8.1 ms at 128 and 13.9 ms at 1024 (blocked). MIN, MAX,
  FIRST and every merge of slab partials (slabs × cap rows) take it.
* `mxu` — `slot_sums`: ALL the integer sums of a slot-addressed aggregate
  (COUNT, SUM, AVG over integers and scaled DECIMALs) as one one-hot
  contraction on the matrix unit over integer pieces of at most 8 bits,
  and of only the bits each value can hold where the caller knows them
  (`SumColumn.bits`, from the cached column bounds): Q1's 29 states at 12
  slots are 24 piece rows and 2.35 ms where the whole widths are 88
  rows and 3.95 ms (7.5 ms masked; PR 41, the host's clock around
  back-to-back calls, so both carry a call's fixed cost; the device's
  reading of the whole-width loop was 2.4 ms, PR 33), Q3/Q5's revenue
  and count 8 rows and 0.54 ms against 24 and 0.90; at whole width 6.6 ms
  at 128 slots (234 masked), 13.6 ms at 1024 (402). Taken from
  SLOT_SUM_MIN_WORK rows × slots up, by
  `executor/device_emit._agg_states`.
* sorted runs — `SortedRuns`, `run_sums`: over rows already sorted by
  group a cumsum, one gather of `cap` elements at the run ends and a
  difference — a packed int64 WORD of states, not a state (PR 44): every
  summed field whose width is known (`SumColumn.bits`, a validity's one
  bit) shares a word with others, with room for its rows' growth, so
  Q18's three limbs and count are one scan and one gather of 3M run ends
  where they were four; a gather costs 0.37 s per 16M elements and plane
  (PR 28). For integer sums beyond MASKED_REDUCE_CAP slots.
* scatter — `jax.ops.segment_*`: serialized updates, 1.1 s an int64 state
  into 8M slots (PR 28); what is left for MIN/MAX beyond the cap.

All functions take `num_segments` statically so jitted shapes stay static.
Rows whose id is out of range (dead rows carry `num_segments`) drop.
"""

from __future__ import annotations

from collections import Counter
from typing import List, NamedTuple, Optional, Sequence

import numpy as np

# Up to this many slots a grouped reduction is a masked broadcast-reduce and
# never a scatter (a TPU scatter serializes its updates: 1.1 s an int64
# state into 8M slots on a v5e, PERF.md §6 PR 28). The reduce stays a fused
# vector reduction, exact in int64, but costs rows × slots select-adds in
# emulated 64 bits: per state and 8M-row slab 0.26 ms at 12 slots, 13.9 ms
# at 1024 (header). The broadcast materializes rows × slots values, so past
# MASKED_REDUCE_WORK of them it runs BLOCKED: lax.map over row blocks, each
# reduced into (cap,) partials — the data streams from HBM once and no
# scatter appears. The integer SUMS of an aggregate leave this kernel for
# the matrix unit from SLOT_SUM_MIN_WORK up (`slot_sums`), and an aggregate
# without GROUP BY asks for ONE segment and reduces flat.
MASKED_REDUCE_CAP = 1024
MASKED_REDUCE_WORK = 1 << 27


def _masked_ok(data, num_segments: int) -> bool:
    return num_segments == 1 or (
        num_segments <= MASKED_REDUCE_CAP and
        int(data.shape[0]) * num_segments <= MASKED_REDUCE_WORK)


def _is_np(xp) -> bool:
    return xp is np


def _masked_reduce(xp, data, segment_ids, num_segments, identity, reducer):
    if num_segments == 1:
        # one segment: a plain reduction over the rows whose id is 0 — no
        # (n, 1) slot axis for the compiler to lay out. Out-of-range ids
        # (dead rows) still drop.
        ident = xp.asarray(identity, dtype=data.dtype)
        return reducer(xp.where(segment_ids == 0, data, ident))[None]
    iota = xp.arange(num_segments, dtype=segment_ids.dtype)
    m = segment_ids[:, None] == iota[None, :]
    ident = xp.asarray(identity, dtype=data.dtype)
    return reducer(xp.where(m, data[:, None], ident), axis=0)


def _blocked_masked_reduce(xp, data, segment_ids, num_segments, identity,
                           reducer):
    """Masked reduce in row blocks of ≤ MASKED_REDUCE_WORK materialized
    cells: lax.map(body, blocks) → (B, cap) partials → combine. Out-of-range
    segment ids (dead-row padding) match no slot and drop, exactly like the
    scatter's mode='drop'."""
    from tidb_tpu.ops.jax_env import lax
    n = int(data.shape[0])
    blk = max(MASKED_REDUCE_WORK // num_segments, 1)
    nb = (n + blk - 1) // blk
    pad = nb * blk - n
    ident = xp.asarray(identity, dtype=data.dtype)
    if pad:
        data = xp.concatenate([data, xp.full(pad, ident, dtype=data.dtype)])
        segment_ids = xp.concatenate(
            [segment_ids,
             xp.full(pad, num_segments, dtype=segment_ids.dtype)])
    data2 = data.reshape(nb, blk)
    gid2 = segment_ids.reshape(nb, blk)
    iota = xp.arange(num_segments, dtype=segment_ids.dtype)

    def body(args):
        d, g = args
        m = g[:, None] == iota[None, :]
        return reducer(xp.where(m, d[:, None], ident), axis=0)

    parts = lax.map(body, (data2, gid2))          # (nb, cap)
    return reducer(parts, axis=0)


CUMSUM_BLOCK = 1 << 20


def _cumsum(x):
    """jnp.cumsum over a long vector, in blocks: the TPU compiler takes
    70 s over one int64 cumsum of 48M elements (6 s at 8M, with `while`
    loops), 1.2 s over the same sum as block-wise cumsums plus the blocks'
    offsets — and no loop (PERF.md §6, PR 28)."""
    from tidb_tpu.ops.jax_env import jnp
    n = x.shape[0]
    if n <= CUMSUM_BLOCK or n % CUMSUM_BLOCK:
        return jnp.cumsum(x)
    c = jnp.cumsum(x.reshape(-1, CUMSUM_BLOCK), axis=1)
    totals = c[:, -1]
    return (c + (jnp.cumsum(totals) - totals)[:, None]).reshape(-1)


class SortedRuns:
    """Segment ids of rows that are ALREADY SORTED by group, so that a
    group is a run of adjacent rows: the scatter-free form of the segment
    sums, for group counts beyond the masked reduce.

    A TPU scatter-add serializes: an int64 `jax.ops.segment_sum` of an
    8M-row slab into 8M slots reads 1.1 s a state on a v5e (PERF.md §6,
    PR 28), where a sort of the slab costs 25–30 ms and an int64 cumsum
    10 ms. So a grouped aggregate over many groups sorts its rows by key
    once (ops/factorize.sort_rows) and every sum is a cumsum, ONE gather
    of `cap` elements at the runs' last rows, and an adjacent difference —
    exact in wrapping int64 arithmetic. That is `sum`, of one vector; an
    aggregate's states go through `run_sums`, which packs every field of
    known width into shared int64 words and calls `sum` once a WORD: the
    gather, the dearest step, is paid a word and not a state. COUNT and
    AVG are sums; MIN and MAX keep the scatter lowering, so an aggregate
    that has one does not take this path.

    `ends` are the positions of the runs' last rows, ascending (garbage
    beyond `n_runs`); slot g of a result is run g in sorted order. With
    more runs than `cap` the results are invalid and `n_runs` says so (the
    caller's capacity ladder retries)."""

    def __init__(self, ends, n_runs, cap: int):
        from tidb_tpu.ops.jax_env import jnp
        n = ends.shape[0]
        if cap > n:
            ends = jnp.concatenate(
                [ends, jnp.full(cap - n, n - 1, dtype=ends.dtype)])
        self.cap = cap
        self.ends = ends[:cap]
        self.n_runs = n_runs
        self.slot_live = jnp.arange(cap, dtype=jnp.int32) < n_runs

    def at_ends(self, x, fill=0):
        """x at each run's last row → (cap,), `fill` in the dead slots."""
        from tidb_tpu.ops.jax_env import jnp
        return jnp.where(self.slot_live, jnp.take(x, self.ends),
                         jnp.asarray(fill, dtype=x.dtype))

    def sum(self, data):
        from tidb_tpu.ops.jax_env import jnp
        # booleans and int32 scan in int32 (there are < 2³¹ rows)
        acc = jnp.int32 if data.dtype in (jnp.bool_, jnp.int32) \
            else data.dtype
        ends = self.at_ends(_cumsum(data.astype(acc)))
        prev = jnp.concatenate([jnp.zeros(1, dtype=ends.dtype), ends[:-1]])
        return jnp.where(self.slot_live, ends - prev, 0) \
            .astype(jnp.int64 if acc == jnp.int32 else data.dtype)


def segment_sum(xp, data, segment_ids, num_segments: int):
    if isinstance(segment_ids, SortedRuns):
        return segment_ids.sum(data)
    if _is_np(xp):
        out = np.zeros(num_segments, dtype=data.dtype)
        np.add.at(out, segment_ids, data)
        return out
    if _masked_ok(data, num_segments):
        return _masked_reduce(xp, data, segment_ids, num_segments,
                              data.dtype.type(0), xp.sum)
    if num_segments <= MASKED_REDUCE_CAP:
        return _blocked_masked_reduce(xp, data, segment_ids, num_segments,
                                      data.dtype.type(0), xp.sum)
    from tidb_tpu.ops.jax_env import jax
    return jax.ops.segment_sum(data, segment_ids, num_segments=num_segments)


# ---------------------------------------------------------------------------
# slot sums on the matrix unit
# ---------------------------------------------------------------------------

#: rows × slots from which the sums of a slot-addressed aggregate go to the
#: matrix unit (header). A merge of slab partials (slabs × cap rows) and
#: a small batch stay far under it and keep the masked reduce.
SLOT_SUM_MIN_WORK = 1 << 18
#: rows of one block of the contraction. A block's int32 accumulator is
#: exact while rows × 128 < 2³¹ (every piece lies in [-128, 128)), so
#: any block below 2²⁴ rows is; this size keeps a block's pieces and its
#: one-hot in the chip's fast memory.
SLOT_SUM_BLOCK = 1 << 17


class SumColumn(NamedTuple):
    """One per-row integer whose sums by slot `slot_sums` computes: bits
    [shift, shift + bits) of `values` — a bool, int32 or int64 vector, or
    plane `plane` of a 2-D (planes, rows) int64 array — read as unsigned,
    or as a signed number when `signed` (the field then ends at the top of
    the dtype); `bits` None = the whole width, signed. A row that `valid`
    (a bool vector) masks out counts 0; `values` None counts the valid
    rows. Two columns that name the SAME arrays and field are computed
    once."""
    values: object
    valid: object = None
    plane: Optional[int] = None
    shift: int = 0
    bits: Optional[int] = None
    signed: bool = True


def _is_mask(col: SumColumn) -> bool:
    """A 0/1 column: a validity to count, or a boolean value."""
    return col.values is None or col.values.dtype == np.bool_


def _column_field(col: SumColumn):
    """→ (shift, bits, signed) with the dtype's width filled in (a
    narrower integer is read from its sign-extended 32-bit word)."""
    if _is_mask(col):
        return 0, 1, False
    if col.bits is not None:
        return col.shift, col.bits, col.signed
    return 0, max(8 * np.dtype(col.values.dtype).itemsize, _WORD), True


def _column_key(col: SumColumn):
    return (id(col.values), col.plane, id(col.valid)) + _column_field(col)


def _column_data(xp, col: SumColumn):
    """The column as one int64 per row: what the other lowerings sum."""
    if col.values is None:
        return col.valid.astype(xp.int64)
    v = col.values if col.plane is None else col.values[col.plane]
    if col.valid is not None:
        v = xp.where(col.valid, v, xp.zeros_like(v))
    shift, bits, signed = _column_field(col)
    v = v.astype(xp.int64) >> shift
    return v if signed else v & xp.int64((1 << bits) - 1)


def slot_sum_lowering(xp, n_rows: int, num_segments: int) -> str:
    """Which kernel sums per-row integers into `num_segments` slots over
    `n_rows` rows: `flat` (one slot: a plain reduction), `mxu` (the
    one-hot contraction of `slot_sums`), `masked` (one broadcast reduce
    per state) or `scatter` (beyond MASKED_REDUCE_CAP). From shapes alone,
    so a trace can say it."""
    if num_segments == 1:
        return "flat"
    if num_segments > MASKED_REDUCE_CAP:
        return "scatter"
    if not _is_np(xp) and n_rows * num_segments >= SLOT_SUM_MIN_WORK:
        return "mxu"
    return "masked"


#: bits of a packed word, and of the words a value is read as
_WORD = 32
#: bits of a piece: what an int8 holds
_PIECE = 8
#: piece rows a word's broadcast makes at once: the sublanes of a vector
#: register, so that the groups stack without moving anything.
_GROUP_ROWS = 8


def _field_chunks(shift: int, bits: int, signed: bool):
    """Cut bits [shift, shift + bits) of a value where its 32-bit words
    meet → [(start, width, signed)], the top chunk signed where the field
    is (a signed field ends at the top of its dtype, so that chunk ends
    at the top of its word)."""
    out = []
    s, end = shift, shift + bits
    while s < end:
        w = min(end - s, _WORD - s % _WORD)
        out.append((s, w, signed and s + w == end))
        s += w
    return out


def _biased(width: int, signed: bool) -> bool:
    """An unsigned byte does not fit int8: it is stored less 128."""
    return width == _PIECE and not signed


class _Plan(NamedTuple):
    """What the contraction holds, from dtypes and fields alone.

    `sources` {(values, plane, validity): (column, reads its high word)}:
    the distinct values. `words` [[(chunk, offset)]]: the packed 32-bit
    words, each the chunks or-ed into it and where; a chunk is `None`
    (the constant 1: the slot's rows), `(source,)` (a 0/1 column) or
    `(source, start, width, signed)` (bits of a value that lie in one of
    its words). `groups` [[(word, start, width, signed)]]: the piece rows,
    _GROUP_ROWS a group, each ≤ 8 bits of one packed word. `where`
    {field: [(row of the piece matrix, shift back, stored less 128)]}:
    the pieces of each distinct column; `where[None]` is the column of
    ones."""
    sources: dict
    words: list
    groups: list
    where: dict


def _slot_sum_plan(columns: Sequence[SumColumn]) -> _Plan:
    """Every distinct column's field is cut where the value's 32-bit
    words meet, the chunks of ALL columns are packed into as few 32-bit
    words as hold them (widest first, each into the first word with
    room; a signed chunk stays at the top of a word, where the value
    holds it), and each chunk is cut into pieces of at most 8 bits: so
    a value that is known to hold 13 bits makes two pieces where its 64
    would make eight, and shares its word with others. A field of no
    bits is the constant 0 and makes none."""
    sources, chunks, fields = {}, {None: (1, False)}, {None: [(None, 0)]}
    for c in columns:
        key = _column_key(c)
        if key in fields:
            continue
        skey, (shift, bits, signed) = key[:3], key[3:]
        if _is_mask(c):
            sources.setdefault(skey, [c, False])
            chunks[skey,] = (1, False)
            fields[key] = [((skey,), 0)]
            continue
        fields[key] = []
        for start, width, top in _field_chunks(shift, bits, signed):
            src = sources.setdefault(skey, [c, False])
            src[1] = src[1] or start >= _WORD
            chunks[skey, start, width, top] = (width, top)
            fields[key].append(((skey, start, width, top), start - shift))
    # pack: [bits taken from the bottom, bits free below a signed chunk]
    words, room = [], []
    for chunk, (width, top) in chunks.items():
        if top:
            words.append([(chunk, _WORD - width)])
            room.append([0, _WORD - width])
    for chunk, (width, top) in sorted(
            chunks.items(), key=lambda kv: -kv[1][0]):
        if top:
            continue
        for at, r in zip(words, room):
            if r[0] + width <= r[1]:
                break
        else:
            at, r = [], [0, _WORD]
            words.append(at)
            room.append(r)
        at.append((chunk, r[0]))
        r[0] += width
    # pieces, in the order of the words: a group is filled before the
    # next begins, so it may hold pieces of two words
    groups, rows = [[]], {}
    for w, at in enumerate(words):
        for chunk, off in sorted(at, key=lambda t: t[1]):
            width, top = chunks[chunk]
            rows[chunk] = []
            for s in range(0, width, _PIECE):
                p = min(_PIECE, width - s)
                signed = top and s + p == width
                if len(groups[-1]) == _GROUP_ROWS:
                    groups.append([])
                rows[chunk].append(
                    (_GROUP_ROWS * (len(groups) - 1) + len(groups[-1]), s,
                     _biased(p, signed)))
                groups[-1].append((w, off + s, p, signed))
    where = {key: [(row, up + s, biased) for chunk, up in at
                   for row, s, biased in rows[chunk]]
             for key, at in fields.items()}
    return _Plan({k: tuple(v) for k, v in sources.items()}, words, groups,
                 where)


def slot_sum_pieces(columns: Sequence[SumColumn],
                    a_word_a_group: bool = False) -> int:
    """Rows of the piece matrix `slot_sums` contracts for these columns
    (the last group's padding included). With `a_word_a_group`, the rows
    it held while every word's pieces were groups of their own (PR 33's
    plan: 88 for Q1's states at whole width) — the yardstick the
    `slot_sums` tag sets the rows beside."""
    groups = _slot_sum_plan(columns).groups
    if not a_word_a_group:
        return _GROUP_ROWS * len(groups)
    per_word = Counter(w for g in groups for w, *_piece in g)
    return _GROUP_ROWS * sum(-(-n // _GROUP_ROWS) for n in per_word.values())


def slot_sums(xp, columns: Sequence[SumColumn], segment_ids,
              num_segments: int) -> List:
    """Σ over the rows of each slot, for every column at once → one
    (num_segments,) int64 array a column, equal bit for bit (wrapping
    modulo 2⁶⁴) to `segment_sum` of the column. Rows whose id is out of
    range drop.

    Where `slot_sum_lowering` says `mxu`, all columns are ONE contraction
    on the matrix unit, in blocks of rows: every distinct column is cut
    into integer pieces of at most 8 bits (an invalid row's value is
    zeroed first), the pieces form an int8 matrix (pieces, rows), the
    slot ids a one-hot int8 matrix (slots, rows), and their product,
    accumulated in int32, holds every piece's sum by slot. An unsigned
    byte p is stored as p − 128 so that it fits int8, and 128 × the slot's
    row count is added back; the blocks' partials are added in int64 and
    the pieces shifted back together on `num_segments` elements. Exact by
    construction: no bit of a column's field is dropped, no accumulator
    can overflow (SLOT_SUM_BLOCK). How many pieces that is follows the
    FIELDS (`_slot_sum_plan`): a caller that knows a value's range names
    the bits it can hold, and the contraction cuts no others.

    The piece matrix is never assembled from row vectors (a vector of
    rows lies across sublanes AND lanes, a matrix row along lanes only:
    stacking 50 vectors cost more than the 29 masked reduces, PERF.md §6
    PR 33): the fields are first packed into 32-bit words by shift-and-or
    on the row vectors, then each packed word is BROADCAST over eight
    sublanes and shifted and masked by a column of eight constants, which
    yields eight piece rows in place, and such groups stack whole."""
    if not columns or slot_sum_lowering(
            xp, int(segment_ids.shape[0]), num_segments) != "mxu":
        return [segment_sum(xp, _column_data(xp, c), segment_ids,
                            num_segments) for c in columns]
    from tidb_tpu.ops.jax_env import jnp, lax
    n = int(segment_ids.shape[0])
    plan = _slot_sum_plan(columns)
    iota = jnp.arange(num_segments, dtype=jnp.int32)[:, None]
    # a group's eight (shift, mask, bias), one a sublane, and which of
    # its words each sublane reads
    consts = []
    for cut in plan.groups:
        c = np.zeros((3, _GROUP_ROWS, 1), dtype=np.int32)
        for r, (_, start, width, top) in enumerate(cut):
            c[:, r, 0] = (start, -1 if top else (1 << width) - 1,
                          128 if _biased(width, top) else 0)
        of = {}
        for r, (w, *_rest) in enumerate(cut):
            of.setdefault(w, np.zeros((_GROUP_ROWS, 1), dtype=bool))[r] = True
        consts.append((c, list(of.items())))

    def block(start, size):
        """Rows [start, start + size) → (num_segments, pieces) int32."""
        def sl(a, plane=None):
            if plane is None:
                return lax.dynamic_slice(a, (jnp.int32(start),), (size,))
            return lax.dynamic_slice(
                a, (jnp.int32(plane), jnp.int32(start)), (1, size))[0]
        read = {}       # source → its 0/1 vector, or its (low, high) words
        for skey, (c, high) in plan.sources.items():
            if _is_mask(c):
                m = sl(c.valid) if c.values is None else sl(c.values)
                if c.values is not None and c.valid is not None:
                    m = m & sl(c.valid)
                read[skey] = m.astype(jnp.int32)
                continue
            v = sl(c.values, c.plane)
            if c.valid is not None:
                v = jnp.where(sl(c.valid), v, jnp.zeros_like(v))
            read[skey] = (v.astype(jnp.int32),
                          (v >> _WORD).astype(jnp.int32) if high else None)
        words = []
        for at in plan.words:
            word = None
            for chunk, off in at:
                if chunk is None:
                    x = jnp.ones(size, dtype=jnp.int32)
                elif len(chunk) == 1:
                    x = read[chunk[0]]
                else:
                    skey, s, width, top = chunk
                    x = read[skey][s // _WORD]
                    if top:         # it lies where it is wanted
                        x, off = x & jnp.int32(-1 << (_WORD - width)), 0
                    elif width < _WORD:
                        x = (x >> (s % _WORD)) & jnp.int32((1 << width) - 1)
                x = x << off if off else x
                word = x if word is None else word | x
            words.append(word)
        pieces = []
        for c, of in consts:
            src = words[of[0][0]][None, :]
            for w, rows in of[1:]:
                src = jnp.where(rows, words[w][None, :], src)
            pieces.append(((src >> c[0]) & c[1]) - c[2])
        onehot = sl(segment_ids).astype(jnp.int32)[None, :] == iota
        return lax.dot_general(
            onehot.astype(jnp.int8),
            jnp.concatenate(pieces, axis=0).astype(jnp.int8),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32)

    nb, tail = divmod(n, SLOT_SUM_BLOCK)
    total = jnp.zeros((num_segments, _GROUP_ROWS * len(plan.groups)),
                      dtype=jnp.int64)
    if nb:
        total = lax.map(
            lambda i: block(i * SLOT_SUM_BLOCK, SLOT_SUM_BLOCK),
            jnp.arange(nb, dtype=jnp.int32)).astype(jnp.int64).sum(axis=0)
    if tail:
        total = total + block(nb * SLOT_SUM_BLOCK, tail).astype(jnp.int64)
    rows = total[:, plan.where[None][0][0]]
    sums = {}
    for key, at in plan.where.items():
        acc = jnp.zeros(num_segments, dtype=jnp.int64)
        for j, up, biased in at:
            piece = total[:, j] + rows * 128 if biased else total[:, j]
            acc = acc + (piece << up)
        sums[key] = acc
    return [sums[_column_key(c)] for c in columns]


# ---------------------------------------------------------------------------
# run sums by packed words
# ---------------------------------------------------------------------------

class _RunPlan(NamedTuple):
    """What `run_sums` scans, from fields and the row count alone.
    `words` [[(column key, offset)]]: the int64 words, each the fields
    or-ed into it and where — or one whole-width column (offset None):
    the scan `SortedRuns.sum` makes. `where` {column key: (word, offset,
    width)}: where each distinct column's sums are cut from (width None =
    the whole word); a column that is the constant 0 has no entry."""
    words: list
    where: dict


def _run_sum_plan(columns: Sequence[SumColumn], n_rows: int) -> _RunPlan:
    """An unsigned field of b bits (a mask: 1) summed over at most
    `n_rows` rows stays under b + ⌈log2(n_rows + 1)⌉ bits — even the
    PREFIX sum over every row does, so packed beside others it never
    carries into its neighbour. Such fields are packed, widest first,
    each into the first word with room (64 bits a word: the top field may
    reach bit 63, the arithmetic wraps and only differences are read). A
    field of no bits is the constant 0 and is not scanned; a column whose
    width is unknown, signed, or past 63 bits with its rows is a word of
    its own at whole width."""
    grow = int(n_rows).bit_length()
    fields, words, where = {}, [], {}
    for c in columns:
        key = _column_key(c)
        if key in fields or key in where:
            continue
        _shift, bits, signed = _column_field(c)
        if signed or bits + grow > 63:
            where[key] = (len(words), 0, None)
            words.append([(key, None)])
        elif bits:
            fields[key] = bits + grow
    room = []           # bits taken of each packed word, from the bottom
    first = len(words)
    for key, width in sorted(fields.items(), key=lambda kv: -kv[1]):
        for w, used in enumerate(room):
            if used + width <= 64:
                break
        else:
            w = len(room)
            room.append(0)
            words.append([])
        where[key] = (first + w, room[w], width)
        words[first + w].append((key, room[w]))
        room[w] += width
    return _RunPlan(words, where)


def run_sum_scans(columns: Sequence[SumColumn], n_rows: int):
    """→ (packed words of fields of known width, words of one column at
    whole width): the scans — a cumsum with its gather at the run ends —
    `run_sums` makes for these columns. From fields and the row count
    alone, so a trace can say it."""
    words = _run_sum_plan(columns, n_rows).words
    whole = sum(1 for at in words if at[0][1] is None)
    return len(words) - whole, whole


def run_sums(columns: Sequence[SumColumn], runs: SortedRuns,
             n_rows: int) -> List:
    """Σ over the rows of each run, for every column at once → one
    (runs.cap,) int64 array a column, equal bit for bit (wrapping modulo
    2⁶⁴) to `runs.sum` of the column — by ONE scan a packed WORD, not one
    a column (`_run_sum_plan`): the word is built by shift-and-or on the
    row vectors (a masked row contributes 0), scanned, gathered at the
    run ends and differenced once (`SortedRuns.sum`), and the fields are
    cut out of the `cap` differences by logical shift and mask. A word of
    one whole-width column is `runs.sum` of it and nothing else."""
    from tidb_tpu.ops.jax_env import jnp, lax
    plan = _run_sum_plan(columns, n_rows)
    by_key = {_column_key(c): c for c in columns}
    sums = []
    for at in plan.words:
        word = None
        for key, off in at:
            x = _column_data(jnp, by_key[key])
            x = x << off if off else x
            word = x if word is None else word | x
        sums.append(runs.sum(word))
    out = []
    for c in columns:
        at = plan.where.get(_column_key(c))
        if at is None:
            out.append(jnp.zeros(runs.cap, dtype=jnp.int64))
            continue
        w, off, width = at
        s = sums[w]
        if width is not None and len(plan.words[w]) > 1:
            s = lax.shift_right_logical(s, jnp.int64(off)) \
                & jnp.int64((1 << width) - 1)
        out.append(s)
    return out


def segment_sum_accurate(xp, data, segment_ids, num_segments: int):
    """Float segment sum with f64-quality accuracy on an f32-only device.

    → (hi, lo) per segment with hi + lo ≈ the exact sum (~48 significant
    bits). TPU has no native f64, and a plain f32 scatter-add absorbs low
    bits once the running sum outgrows individual addends (rel. error up
    to O(n·ε) ≈ 1e-2 at 60M rows). Instead: scale every value by a traced
    power of two (exponent shift — exact in f32), round to int64, and
    accumulate with EXACT integer segment adds; the int result splits back
    into a two-float (hi, lo) pair. Error bound: |err| ≤ n·2⁻ᵏ⁻¹ absolute,
    with 2ᵏ ≈ 2⁶¹/(n·max|x|) — ~1e-12 relative at SF=10 scales.
    Non-finite inputs bypass the int path and propagate (inf/nan) through
    a plain float side-sum. CPU/np accumulates f64 directly (hi, lo=0).
    """
    if _is_np(xp):
        out = np.zeros(num_segments, dtype=np.float64)
        np.add.at(out, segment_ids, data.astype(np.float64))
        return out, np.zeros_like(out)
    if data.dtype == xp.float64:      # CPU jax backend: f64 is native
        s = segment_sum(xp, data, segment_ids, num_segments)
        return s, xp.zeros_like(s)
    finite = xp.isfinite(data)
    x = xp.where(finite, data, xp.zeros_like(data)).astype(xp.float32)
    n_rows = data.shape[0]
    absmax = xp.max(xp.abs(x)) if n_rows else xp.float32(0)
    k = xp.floor(61.0 - xp.log2(xp.maximum(absmax, xp.float32(1e-30)) *
                                (n_rows + 1)))
    k = xp.clip(k, -96.0, 61.0).astype(xp.float32)
    # exp2 is a polynomial approximation on TPU (exp2(30) ≠ 2^30!);
    # ldexp builds the exponent bits exactly, keeping x*scale lossless
    scale = xp.ldexp(xp.float32(1.0), k.astype(xp.int32))
    scaled = xp.round(x * scale).astype(xp.int64)
    ints = segment_sum(xp, scaled, segment_ids, num_segments)
    inv = xp.ldexp(xp.float32(1.0), (-k).astype(xp.int32))
    hi = ints.astype(xp.float32) * inv
    resid = ints - xp.round(hi * scale).astype(xp.int64)
    lo = resid.astype(xp.float32) * inv
    nonfin = segment_sum(xp, xp.where(finite, xp.zeros_like(data), data),
                         segment_ids, num_segments)
    hi = hi + nonfin                  # 0 normally; propagates inf/nan
    return hi, lo


def two_float_add(xp, ahi, alo, bhi, blo):
    """(ahi+alo) + (bhi+blo) as a renormalized two-float pair (Knuth
    two-sum; XLA preserves IEEE ordering so the trick survives jit)."""
    s = ahi + bhi
    bb = s - ahi
    err = (ahi - (s - bb)) + (bhi - bb)
    e = err + alo + blo
    hi = s + e
    lo = e - (hi - s)
    return hi, lo


def segment_count(xp, mask, segment_ids, num_segments: int):
    """Count of True rows per segment → int64."""
    if _is_np(xp):
        out = np.zeros(num_segments, dtype=np.int64)
        np.add.at(out, segment_ids, mask.astype(np.int64))
        return out
    return segment_sum(xp, mask.astype(xp.int64), segment_ids, num_segments)


def segment_min(xp, data, segment_ids, num_segments: int):
    if _is_np(xp):
        out = np.full(num_segments, _max_identity(data.dtype),
                      dtype=data.dtype)
        np.minimum.at(out, segment_ids, data)
        return out
    if _masked_ok(data, num_segments):
        return _masked_reduce(xp, data, segment_ids, num_segments,
                              _max_identity(data.dtype), xp.min)
    if num_segments <= MASKED_REDUCE_CAP:
        return _blocked_masked_reduce(xp, data, segment_ids, num_segments,
                                      _max_identity(data.dtype), xp.min)
    from tidb_tpu.ops.jax_env import jax
    return jax.ops.segment_min(data, segment_ids, num_segments=num_segments)


def segment_max(xp, data, segment_ids, num_segments: int):
    if _is_np(xp):
        out = np.full(num_segments, _min_identity(data.dtype),
                      dtype=data.dtype)
        np.maximum.at(out, segment_ids, data)
        return out
    if _masked_ok(data, num_segments):
        return _masked_reduce(xp, data, segment_ids, num_segments,
                              _min_identity(data.dtype), xp.max)
    if num_segments <= MASKED_REDUCE_CAP:
        return _blocked_masked_reduce(xp, data, segment_ids, num_segments,
                                      _min_identity(data.dtype), xp.max)
    from tidb_tpu.ops.jax_env import jax
    return jax.ops.segment_max(data, segment_ids, num_segments=num_segments)


def segment_any(xp, mask, segment_ids, num_segments: int):
    """True iff any True row lands in the segment."""
    if _is_np(xp):
        out = np.zeros(num_segments, dtype=bool)
        np.logical_or.at(out, segment_ids, mask)
        return out
    return segment_max(xp, mask.astype(xp.int32), segment_ids,
                       num_segments) > 0


def segment_first(xp, data, mask, segment_ids, num_segments: int):
    """First masked value per segment, in row order → (values, found)."""
    n = data.shape[0]
    if _is_np(xp):
        idx = np.full(num_segments, n, dtype=np.int64)
        rows = np.where(mask, np.arange(n, dtype=np.int64), n)
        np.minimum.at(idx, segment_ids, rows)
        found = idx < n
        safe = np.where(found, idx, 0)
        return data[safe], found
    rows = xp.where(mask, xp.arange(n, dtype=xp.int64), n)
    idx = segment_min(xp, rows, segment_ids, num_segments)
    found = idx < n
    safe = xp.where(found, idx, 0)
    return data[safe], found


def _max_identity(dtype):
    dtype = np.dtype(dtype)
    if dtype.kind in "iu":
        return np.iinfo(dtype).max
    if dtype.kind == "f":
        return np.inf
    if dtype.kind == "b":
        return True
    raise AssertionError(f"no identity for {dtype}")


def _min_identity(dtype):
    dtype = np.dtype(dtype)
    if dtype.kind in "iu":
        return np.iinfo(dtype).min
    if dtype.kind == "f":
        return -np.inf
    if dtype.kind == "b":
        return False
    raise AssertionError(f"no identity for {dtype}")
