"""Device factorization & ordering primitives (the no-hash-table kernels).

The reference's hash aggregation and hash join both revolve around an
open-address hash table (executor/aggregate.go getGroupKey→HashGroupKey,
executor/hash_table.go hashRowContainer). TPUs have no efficient random
scatter, so the TPU-native formulation is sort-based (SURVEY §7 stage 4):

  * `factorize` — dense group ids via `lax.sort` (XLA's bitonic sort
    vectorizes on the VPU), boundary detection between sorted neighbors,
    and a cumsum. This is EXACT — actual typed key values (or exact dense
    rank packings of them) are the sort operands, not a 64-bit hash — so
    unlike a hash table there are no collisions to verify.
  * `topn` / `sort_perm` — MySQL ORDER BY semantics (NULLs first ASC, last
    DESC) as sorts returning a gather permutation.

Multi-key operations chain-pack: one NARROW sort per key produces dense
per-key ranks, ranks pack into a single int64 code (re-densified each
step so the domain never overflows), and one final 3-operand sort works
on the packed code. Rationale: on the TPU toolchain, `lax.sort` COMPILE
time explodes with operand count (a 6-operand sort compiles ~10× slower
than a 4-operand one — measured 80-100s vs 9s on the same shapes), so k
narrow sorts beat one wide sort by an order of magnitude in compile
time at equal runtime complexity.

All group counts are static (`cap`): callers get `n_groups` back and must
retry with a bigger cap (or fall back to host) when `n_groups > cap` —
the padding/masking discipline of SURVEY §7 "dynamic shapes vs XLA".
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from tidb_tpu.ops.jax_env import jax, jnp, lax


def _not(flag):
    return jnp.logical_not(flag)


def _key_operands(keys: Sequence[Tuple], live) -> List:
    """Sort operands for [(values, valid-or-None)] keys: dead rows last,
    NULL group before non-NULL, NULL slots canonicalized (outer-join null
    extension leaves garbage there — all NULLs must form ONE group)."""
    operands: List = [_not(live)]
    for v, m in keys:
        v = jnp.asarray(v)
        if m is None:
            operands.append(v)
        else:
            m = jnp.asarray(m)
            operands.append(m)
            operands.append(jnp.where(m, v, jnp.zeros_like(v)))
    return operands


def _dense1(v, m, live):
    """Dense codes of ONE key column — sort + boundary scan, no segment
    ops. Dead rows get arbitrary (larger) codes; callers mask them."""
    n = live.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    operands = _key_operands([(v, m)], live)
    operands.append(iota)
    out = lax.sort(tuple(operands), num_keys=len(operands) - 1)
    sidx = out[-1]
    diff = jnp.zeros(n, dtype=bool).at[0].set(True)
    for comp in out[1:-1]:
        diff = diff | jnp.concatenate(
            [jnp.ones(1, dtype=bool), comp[1:] != comp[:-1]])
    gid_s = jnp.cumsum(diff.astype(jnp.int32)) - 1
    return jnp.zeros(n, dtype=jnp.int32).at[sidx].set(gid_s)


def pack_codes(keys: Sequence[Tuple], live):
    """One int64 code per row identifying the multi-key tuple, via one
    narrow sort per key + packed re-densify (see module docstring for why
    this beats one wide sort). Codes are rank-ordered, so sorting by them
    reproduces lexicographic key order, NULLs-first per column. The LAST
    pack step skips the re-densify sort — a dense·(n+1)+dense product is
    < (n+1)², which fits int64 for any real row count."""
    n = live.shape[0]
    code = None
    for i, (v, m) in enumerate(keys):
        g = _dense1(v, m, live)
        if code is None:
            code = g.astype(jnp.int64)
            continue
        code = code * jnp.int64(n + 1) + g.astype(jnp.int64)
        if i < len(keys) - 1:     # keep the running domain < n+1
            code = _dense1(code, None, live).astype(jnp.int64)
    return code


def factorize(keys: Sequence[Tuple], live, cap: int):
    """Dense group ids for rows under multi-column keys.

    keys: list of (values, valid) pairs — `valid` False means SQL NULL,
          which forms its own group (MySQL GROUP BY semantics, mirroring
          host factorize_columns in executor/hash_agg.py).
    live: (N,) bool — False rows (padding / filtered-out) join no group.
    cap:  static maximum number of groups.

    Returns (gids, n_groups, rep):
      gids     (N,) int32 in [0, cap) — dead rows get an arbitrary in-range
               id; callers must mask their contributions.
      n_groups () int32 — may exceed cap, in which case results are invalid
               and the caller must retry with a larger cap.
      rep      (cap,) int32 — smallest original row index of each group
               (clamped to N-1 for empty slots; gather-safe).
    """
    if len(keys) > 1:
        # chain-pack: narrow per-key sorts, then ONE 3-operand sort
        code = pack_codes(keys, live)
        keys = [(code, None)]
    n = live.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    operands = _key_operands(keys, live)
    operands.append(iota)
    out = lax.sort(tuple(operands), num_keys=len(operands) - 1)
    sidx = out[-1]
    dead_s = out[0]
    live_s = _not(dead_s)
    first = jnp.zeros(n, dtype=bool).at[0].set(True)
    diff = first
    for comp in out[1:-1]:
        diff = diff | jnp.concatenate(
            [jnp.ones(1, dtype=bool), comp[1:] != comp[:-1]])
    boundary = diff & live_s
    gid_s = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    n_groups = boundary.sum().astype(jnp.int32)
    gid_s = jnp.clip(gid_s, 0, cap - 1)
    gids = jnp.zeros(n, dtype=jnp.int32).at[sidx].set(gid_s)
    rep = jax.ops.segment_min(jnp.where(live_s, sidx, n), gid_s,
                              num_segments=cap)
    rep = jnp.minimum(rep, n - 1).astype(jnp.int32)
    return gids, n_groups, rep


WORD_BITS = 62      # a packed key word stays a non-negative int64


# How a grouped aggregate's partials find their groups — the three
# lowerings, named where they are chosen (agg_slabs.chain_key_bounds,
# tree_fragment.tree_agg_key_bounds) and read everywhere else:
SLOTS = "slots"          # key bounds address the group slots directly
RUNS = "runs"            # key bounds pack the keys into sort words: sorted runs
FACTORIZE = "factorize"  # no bounds: per-slab sort-factorize, ladder, merge


class KeyBounds(NamedTuple):
    """What an aggregate's programs are compiled against, read from the
    device cache's per-column bounds. Per-group-key (lo, hi) value bounds
    and what the partials do with them: SLOTS (device_emit._perfect_groups;
    a small packed domain), RUNS (`pack_words`; the domain may be as large
    as it likes) or FACTORIZE (no bounds: the sort-factorize; so does an
    aggregate with no KeyBounds at all, None). And per aggregate the WIDTH
    of its argument, `arg_bits`: every value lies in [0, 2^bits)
    (expression/ranges.sum_bits; None = unknown, () = none known) — what
    the contraction of `ops/segment.slot_sums` cuts its pieces by, and
    what the sorted-runs finalize packs its scanned words by
    (`ops/segment.run_sums`). The width and not the range: a minimum that
    moves, or a maximum that stays under its power of two, mints no
    program."""
    mode: str
    bounds: Tuple[Tuple[int, int], ...]
    arg_bits: Tuple[Optional[int], ...] = ()


def grouping_mode(key_bounds: Optional[KeyBounds]) -> str:
    return FACTORIZE if key_bounds is None else key_bounds.mode


def widths_sig(key_bounds: Optional[KeyBounds]) -> str:
    """The arguments' widths as a program signature holds them
    (`|ab=13,-,30`: widths, never the bounds' values), nothing where none
    is known."""
    if key_bounds is None or not key_bounds.arg_bits:
        return ""
    return "|ab=" + ",".join(
        "-" if b is None else str(b) for b in key_bounds.arg_bits)


def bounds_sig(key_bounds: Optional[KeyBounds]) -> str:
    """The bounds' part of a program signature (a SLOTS signature reads as
    it did when the bounds were a bare list, so cached programs keep
    their names), the arguments' widths after it where any is known —
    but not for RUNS: there this names the SLAB programs, which only hand
    out rows, and with them the statement's `aggrows`; the widths are
    trace constants of the finalize alone, which appends `widths_sig`
    itself (agg_slabs._runs_finalize), so a width that moves renames no
    slab program and no shared sort."""
    if key_bounds is None:
        return "None"
    text = repr(list(key_bounds.bounds))
    if key_bounds.mode == RUNS:
        return RUNS + text
    if key_bounds.mode != SLOTS:
        text = key_bounds.mode + text
    return text + widths_sig(key_bounds)


def choose_key_bounds(bounds, domain: int, slot_cap: int, domain_cap: int,
                      runs_ok: bool, arg_bits=()) -> Optional[KeyBounds]:
    """The one place that picks a lowering from the keys' bounds (`bounds`
    None: some key has none): a domain over `slot_cap` slots groups by
    sorted runs where the aggregates allow it (`runs_ok`) and every key's
    code fits a word; else up to `domain_cap` slots are addressed
    directly; else the sort-factorize. `arg_bits` ride along in every
    lowering — the slots' and the factorize's contraction cuts its pieces
    by them, the sorted runs' finalize packs its scans by them — and are
    dropped where none is known."""
    arg_bits = tuple(arg_bits) if any(b is not None for b in arg_bits) \
        else ()
    if bounds is not None:
        bounds = tuple(bounds)
        if domain > slot_cap and runs_ok and \
                all(_bits(lo, hi) <= WORD_BITS for lo, hi in bounds):
            return KeyBounds(RUNS, bounds, arg_bits)
        if domain <= domain_cap:
            return KeyBounds(SLOTS, bounds, arg_bits)
    return KeyBounds(FACTORIZE, (), arg_bits) if arg_bits else None


def _bits(lo: int, hi: int) -> int:
    return max(int(hi - lo + 1).bit_length(), 1)     # codes 0..hi-lo+1


def pack_words(keys: Sequence[Tuple],
               bounds: Sequence[Tuple[int, int]]) -> List:
    """Keys with known (lo, hi) bounds → as few int64 words as hold them:
    per key the code 0 for NULL and 1 + v - lo otherwise, earlier keys in
    the higher bits, a new word where 62 bits are full. Rows with equal
    keys, and only they, have equal words."""
    words, word, used = [], None, 0
    for (v, m), (lo, hi) in zip(keys, bounds):
        bits = _bits(lo, hi)
        code = jnp.where(jnp.asarray(m),
                         jnp.clip(jnp.asarray(v).astype(jnp.int64), lo, hi)
                         - (lo - 1), jnp.int64(0))
        if word is not None and used + bits > WORD_BITS:
            words.append(word)
            word, used = None, 0
        word = code if word is None else (word << bits) | code
        used += bits
    words.append(word)
    return words


def unpack_words(words: Sequence, bounds: Sequence[Tuple[int, int]],
                 dtypes: Sequence) -> List[Tuple]:
    """The inverse of `pack_words` → [(values, valid)]."""
    layout, w, used = [], 0, 0
    for lo, hi in bounds:
        bits = _bits(lo, hi)
        if used and used + bits > WORD_BITS:
            w, used = w + 1, 0
        layout.append((w, bits))
        used += bits
    out = [None] * len(bounds)
    shift = {}
    for i in reversed(range(len(bounds))):      # last key in the low bits
        w, bits = layout[i]
        code = (words[w] >> shift.get(w, 0)) & jnp.int64((1 << bits) - 1)
        shift[w] = shift.get(w, 0) + bits
        lo = bounds[i][0]
        out[i] = ((code + (lo - 1)).astype(dtypes[i]), code != 0)
    return out


DEAD_WORD = (1 << 63) - 1      # above every packed word: dead rows last


def sort_rows(words: Sequence, live, payloads: Sequence):
    """Sort rows by their key words, dead rows last, carrying `payloads` →
    {"words", "payloads", "ends", "n_runs"}: the sorted arrays, the
    positions of the last row of each run of equal words (ascending, then
    garbage) and the number of runs.

    What the TPU compiler charges for a sort is its comparator, once per
    distinct sort in a program and again in every program (52 s for an
    int64 key, 20 s for an int32 one, 25–30 s more per operand, whatever
    the length — PERF.md §6, PR 28). So the dead flag rides in the words
    (DEAD_WORD) instead of a key operand of its own, the run ends come
    from a ONE-operand uint32 sort (flag in the top bit, position below),
    and this function is its own program, shared by every statement
    (agg_slabs._SortRowsProgram): no statement's programs hold a sort."""
    n = live.shape[0]
    words = [jnp.where(live, w, jnp.int64(DEAD_WORD)) for w in words]
    out = lax.sort(tuple(words) + tuple(payloads), num_keys=len(words))
    words_s = list(out[:len(words)])
    live_s = words_s[0] != jnp.int64(DEAD_WORD)
    differs = jnp.zeros(n - 1, dtype=bool)
    for w in words_s:
        differs = differs | (w[1:] != w[:-1])
    # last live row of a run: the next row starts a run, is dead, or none
    is_last = live_s & jnp.concatenate([differs, jnp.ones(1, dtype=bool)])
    assert n < (1 << 31)
    tagged = jnp.where(is_last, jnp.uint32(0), jnp.uint32(1 << 31)) | \
        jnp.arange(n, dtype=jnp.uint32)
    ends = (lax.sort(tagged) & jnp.uint32((1 << 31) - 1)).astype(jnp.int32)
    return {"words": words_s, "payloads": list(out[len(words):]),
            "ends": ends, "n_runs": is_last.sum().astype(jnp.int32)}


def topn_select(keys: Sequence[Tuple], descs: Sequence[bool], live, k: int):
    """Top-k row indices under ORDER BY semantics WITHOUT a sort →
    (idx (k,), n_out): k rounds of a lexicographic arg-best over the
    remaining rows, each key narrowing the candidates to those that tie
    on it (the last tie goes to the lowest row index). k is an ORDER BY …
    LIMIT's, tens to hundreds; a round is a handful of reductions over
    the rows, and the loop compiles once — against 50 s and more for a
    multi-key sort's comparator."""
    n = live.shape[0]
    vals = []
    for (v, m), desc in zip(keys, descs):
        v, m = jnp.asarray(v), jnp.asarray(m)
        if v.dtype == jnp.bool_:
            v = v.astype(jnp.int32)
        # best = greatest of (null rank, value): ASC wants NULLs first and
        # small values, DESC NULLs last and great ones
        null_rank = _not(m) if not desc else m
        vals.append((null_rank.astype(jnp.int32),
                     jnp.where(m, v if desc else -v if v.dtype.kind == "f"
                               else ~v, jnp.zeros_like(v))))
    iota = jnp.arange(n, dtype=jnp.int32)

    def pick(_, carry):
        left, idx, i = carry
        cand = left
        for part in [p for pair in vals for p in pair]:
            lo = jnp.iinfo(part.dtype).min if part.dtype.kind in "iu" \
                else -jnp.inf
            best = jnp.max(jnp.where(cand, part, lo))
            cand = cand & (part == best)
        row = jnp.min(jnp.where(cand, iota, n))
        row = jnp.minimum(row, n - 1).astype(jnp.int32)
        return (left & (iota != row), idx.at[i].set(row), i + 1)

    _, idx, _ = lax.fori_loop(
        0, k, pick, (live, jnp.zeros(k, dtype=jnp.int32), jnp.int32(0)))
    return idx, jnp.minimum(live.sum().astype(jnp.int32), jnp.int32(k))


def _order_operands(keys: Sequence[Tuple], descs: Sequence[bool], live):
    """Sort operands implementing MySQL ORDER BY over possibly-NULL keys."""
    operands: List = [_not(live)]  # dead rows last
    for (v, m), desc in zip(keys, descs):
        v = jnp.asarray(v)
        m = jnp.asarray(m)
        if desc:
            operands.append(_not(m))          # DESC: NULLs last
            if v.dtype.kind == "f":
                operands.append(-v)
            elif v.dtype == jnp.bool_:
                operands.append(_not(v))
            else:
                operands.append(~v)           # exact order flip, no overflow
        else:
            operands.append(m)                # ASC: NULLs first
            operands.append(v)
    return operands


def sort_perm(keys: Sequence[Tuple], descs: Sequence[bool], live):
    """Full-sort permutation → (perm (N,) int32, n_live () int32).

    perm[0:n_live] are original row indices in output order; the tail is
    the dead rows (stable, but callers trim via n_live). Multi-key orders
    chain-pack into per-key dense RANKS (order-preserving, so the packed
    code sorts exactly like the key list; DESC flips the rank, which also
    sends NULLs last per MySQL)."""
    n = live.shape[0]
    if len(keys) > 1:
        code = None
        for i, ((v, m), desc) in enumerate(zip(keys, descs)):
            g = _dense1(v, m, live)        # rank-ordered, NULLs first
            if desc:
                g = jnp.int32(n) - g       # flip order, NULLs last
            if code is None:
                code = g.astype(jnp.int64)
                continue
            code = code * jnp.int64(n + 1) + g.astype(jnp.int64)
            if i < len(keys) - 1:
                code = _dense1(code, None, live).astype(jnp.int64)
        operands: List = [_not(live), code]
    else:
        operands = _order_operands(keys, descs, live)
    operands.append(jnp.arange(n, dtype=jnp.int32))
    out = lax.sort(tuple(operands), num_keys=len(operands) - 1,
                   is_stable=True)
    return out[-1], live.sum().astype(jnp.int32)


def topn(keys: Sequence[Tuple], descs: Sequence[bool], live, k: int):
    """Top-k row indices under ORDER BY semantics → (idx (k,), n_out)."""
    perm, n_live = sort_perm(keys, descs, live)
    return perm[:k], jnp.minimum(n_live, jnp.int32(k))


def dense_codes(keys: Sequence[Tuple], live):
    """Dense group codes ONLY — factorize without the representative-row
    segment_min (a num_segments=N scatter the join's key-combining never
    uses)."""
    if len(keys) == 1:
        return _dense1(keys[0][0], keys[0][1], live)
    return pack_codes(keys, live)


def distinct_pair_factorize(gids, values, validity, live, cap: int):
    """Dense ids of live (group, value) pairs → (first_mask, pair_gids,
    n_pairs, rep). One value-rank sort + one packed-code sort, shared
    between DISTINCT state masking (first_mask) and the cross-slab
    distinct-pair partials (rep/n_pairs) — the device half of the
    reference's per-group hash sets (aggfuncs/func_count_distinct.go)."""
    n = live.shape[0]
    pair_live = live & jnp.asarray(validity)
    vid = _dense1(jnp.asarray(values), None, pair_live)
    code = jnp.asarray(gids).astype(jnp.int64) * jnp.int64(n + 1) + \
        vid.astype(jnp.int64)
    pg, n_pairs, rep = factorize([(code, None)], pair_live, cap)
    iota = jnp.arange(n, dtype=jnp.int32)
    first = jnp.take(rep, pg) == iota
    return first, pg, n_pairs, rep


def distinct_mask(gids, values, validity, live):
    """True at the first live+valid occurrence of each (group, value) pair.
    Rows where validity/live is False return garbage; callers keep masking
    with validity & live as usual."""
    n = live.shape[0]
    return distinct_pair_factorize(gids, values, validity, live, n)[0]
