"""Compressed physical column layouts for the device cache.

Per-column layout chosen once at encode time ("Fine-Tuning Data
Structures for Analytical Query Processing" — the load-time layout
decision is the highest-leverage lever for scan-bound analytics):

  * pack — frame-of-reference bit-packing: codes are `value - ref`
    (ref = min over valid values, so negative ints need no zig-zag)
    packed at the observed bit width into uint32 words;
  * dict — low-cardinality int columns store sorted-dictionary rank
    codes (the string-dictionary idea extended to ints), packed at the
    code width, with ONE shared dictionary values array per column;
  * delta — monotonically non-decreasing, fully-valid int columns
    (sorted PKs, event timestamps) store successive differences packed
    at the max-gap width, with a per-slab base value; decode is a
    cumulative sum — on the host one `np.cumsum`, in a trace a narrow
    scan inside blocks of `DELTA_BLOCK` rows plus the blocks' bases
    (`delta_scan`, `_delta_values`). Constant runs pack at the zero-diff
    width, so delta subsumes run-length encoding for sorted data.

The layout decision is workload-adaptive: `choose_layout` accepts
hints distilled from the Registry's per-digest profiles (group-by
heavy workloads raise the dictionary cardinality cap — dictionary
codes feed group factorization directly).

Width is rounded up to {0, 1, 2, 4, 8, 16, 32} so codes never straddle
a word boundary and the device decode is a gather-free broadcast
shift/mask. Width 0 means every valid value equals `ref` (single
distinct value, or an all-NULL column) — no words are stored at all.
Validity masks are themselves bit-packed at width 1 over the padded
slab, so a compressed slab is (words, mask_words[, dictvals]) and the
raw representation never crosses PCIe.

Decode is xp-generic (numpy for the CPU oracle in tests, jnp inside
traced fragments via device_emit.emit_decode) and byte-exact: packing
the PADDED slab preserves the False padding of the mask, and invalid
slots pack as code 0 — their decoded values are don't-care because
every consumer masks by validity.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

from tidb_tpu.errors import LayoutError

#: legal packed widths — each divides the 32-bit word exactly
WIDTHS = (0, 1, 2, 4, 8, 16, 32)
WORD_BITS = 32
#: dictionary layout only below this cardinality (TiFlash's low-card
#: dictionary threshold is the same order of magnitude)
DICT_CARD_CAP = 4096
#: rows per block of the traced `delta` decode (`_delta_values`). Chosen on
#: the chip (PERF.md §6, PR 29): log-step scans of an 8M-row slab read
#: 0.23–0.37 ms at 128 and 0.29–0.40 ms at 1024, but 128 leaves 65,536
#: block totals whose 64-bit scan alone costs the TPU compiler 4–7 s a
#: program, 1024 leaves 8192 (1.3–2.7 s). Every slab capacity of the
#: device cache is a power of two ≥ 1024, so a block always divides it.
DELTA_BLOCK = 1024


@dataclass(frozen=True)
class ColLayout:
    """Static per-column layout descriptor — hashable and data-light so
    it keys program signatures (escalation recompiles stay exact-need)."""

    kind: str      # "pack" (FoR) | "dict" (dictionary) | "delta" (diffs)
    width: int     # bits per packed code — one of WIDTHS
    ref: int       # frame-of-reference base (pack); 0 for dict/delta
    dtype: str     # logical numpy dtype name the decode restores
    card: int = 0  # dictionary cardinality (dict kind only)

    def sig(self) -> str:
        return (f"{self.kind}:w{self.width}:r{self.ref}:"
                f"c{self.card}:{self.dtype}")

    @property
    def np_dtype(self) -> np.dtype:
        return np.dtype(self.dtype)


def validate(layout) -> None:
    """Reject a corrupted/inconsistent descriptor with a typed error —
    consumers call this BEFORE decoding, so a bad descriptor can never
    reach the traced decode and produce silently wrong rows."""
    if not isinstance(layout, ColLayout):
        raise LayoutError(
            f"column layout descriptor is not a ColLayout: {layout!r}")
    if layout.kind not in ("pack", "dict", "delta"):
        raise LayoutError(f"unknown layout kind {layout.kind!r}")
    if layout.kind == "delta" and layout.width == 0:
        raise LayoutError("delta layout with width 0 (constant columns "
                          "must use pack width 0)")
    if layout.width not in WIDTHS:
        raise LayoutError(
            f"illegal packed width {layout.width} (legal: {WIDTHS})")
    try:
        dt = np.dtype(layout.dtype)
    except TypeError as e:
        raise LayoutError(
            f"layout dtype {layout.dtype!r} is not a dtype") from e
    if dt.kind not in "iu":
        raise LayoutError(
            f"layout dtype {layout.dtype!r} is not an integer type")
    if layout.kind == "dict" and layout.card <= 0:
        raise LayoutError(
            f"dict layout with non-positive cardinality {layout.card}")


def _round_width(bits: int) -> Optional[int]:
    for w in WIDTHS:
        if bits <= w:
            return w
    return None


def choose_layout(vals: np.ndarray, valid: np.ndarray,
                  allow_dict: bool = True, hints: Optional[dict] = None
                  ) -> Tuple[Optional[ColLayout], Optional[np.ndarray]]:
    """GLOBAL per-column layout decision → (layout or None, dictvals).

    Over the FULL column so every slab shares one layout (and one
    program signature). Floats, wide decimals (never integer dtype
    here) and columns whose observed range needs more than half the
    logical width stay raw — compression must at least halve the value
    bytes to be worth a layout.

    `hints` carries workload evidence distilled from the per-digest
    statement profiles (device_cache.workload_hints): a group-by-heavy
    workload sets "group_heavy", which raises the dictionary
    cardinality cap 4× and lets dictionary win width ties — dict codes
    double as pre-factorized group ids, so the wider cap pays for
    itself on the agg side even when pack would be byte-equal."""
    from tidb_tpu.util.observability import first_touch
    hints = hints or {}
    dt = vals.dtype
    if dt.kind not in "iu" or dt.itemsize > 8:
        return None, None
    max_width = dt.itemsize * 8 // 2
    name = dt.name
    with first_touch("layout"):
        all_valid = bool(valid.all())
        vv = vals if all_valid else vals[valid]
        if vv.size == 0:
            # all-NULL column: width 0, nothing stored but the packed mask
            return ColLayout("pack", 0, 0, name), None
        lo, hi = int(vv.min()), int(vv.max())
        pw = _round_width((hi - lo).bit_length())
        pack = ColLayout("pack", pw, lo, name) \
            if pw is not None and pw <= max_width else None
        # sorted fully-valid columns (PKs, timestamps): successive diffs
        # need max-gap bits, not range bits — a dense sorted PK packs at
        # width 1-2 regardless of its absolute range
        if all_valid and vv.size >= 2:
            v64 = vv.astype(np.int64)
            diffs = np.diff(v64)
            if diffs.size and int(diffs.min()) >= 0 \
                    and int(diffs.max()) > 0:
                xw = _round_width(int(diffs.max()).bit_length())
                if xw is not None and 0 < xw <= max_width and \
                        (pack is None or xw < pack.width):
                    pack = ColLayout("delta", xw, 0, name)
    if allow_dict and (pack is None or pack.width > 1):
        with first_touch("dict"):
            uniq = np.unique(vv)
        card = int(uniq.size)
        dict_cap = DICT_CARD_CAP * (4 if hints.get("group_heavy") else 1)
        if card <= dict_cap:
            dw = _round_width(max(card - 1, 0).bit_length())
            better = dw is not None and dw <= max_width and (
                pack is None or dw < pack.width or
                (hints.get("group_heavy") and dw == pack.width and
                 pack.kind == "pack"))
            if better:
                return ColLayout("dict", dw, 0, name, card), uniq
    return pack, None


def _pack_codes(codes: np.ndarray, width: int) -> np.ndarray:
    """Non-negative uint64 codes (< 2^width) → uint32 words in PLANAR
    order: with `per` codes to a word and n_words words, code k sits in
    word k % n_words at bits [j*width, (j+1)*width), j = k // n_words —
    plane j is the contiguous run of codes [j*n_words, (j+1)*n_words).

    Planar, not interleaved (code k in word k // per), for the decode on
    the chip: unpacking a plane is one shift/mask over the whole word
    array, and the planes concatenate with the row axis kept minor-most.
    The interleaved order needs a (n_words, per) → (n_words*per,)
    reshape whose minor dimension is 2..32 wide; the TPU compiler
    relayouts that tile by tile and took a minute per column at 256K
    rows (longer with size) before any program ran."""
    per = WORD_BITS // width
    n = codes.shape[0]
    n_words = -(-n // per)
    if n_words * per != n:
        pad = np.zeros(n_words * per, dtype=np.uint64)
        pad[:n] = codes
        codes = pad
    codes = codes.reshape(per, n_words)
    shifts = np.arange(per, dtype=np.uint64) * np.uint64(width)
    words = np.bitwise_or.reduce(codes << shifts[:, None], axis=0)
    return words.astype(np.uint32)


def pack_slab(layout: ColLayout, vals: np.ndarray, mask: np.ndarray,
              dictvals: Optional[np.ndarray] = None):
    """Host-side encode of ONE padded slab → (words, mask_words) — plus
    a trailing per-slab base array for delta slabs. Invalid/padding
    slots pack as code 0 (decoded values there are don't-care —
    consumers mask by validity); the mask packs the padded slab
    exactly, so decode restores it byte-for-byte."""
    mask = np.asarray(mask, dtype=bool)
    mask_words = _pack_codes(mask.astype(np.uint64), 1)
    if layout.width == 0:
        # nothing to store: every valid value IS layout.ref
        return np.zeros(1, dtype=np.uint32), mask_words
    if layout.kind == "dict":
        safe = np.where(mask, vals, dictvals[0])
        codes = np.searchsorted(dictvals, safe).astype(np.uint64)
    elif layout.kind == "delta":
        # delta columns are fully valid, so the valid prefix IS the
        # slab's rows; padding diffs stay 0 (cumsum holds the last
        # value there, masked out by the packed validity)
        n = int(mask.sum())
        v64 = vals.astype(np.int64)
        codes = np.zeros(vals.shape[0], dtype=np.uint64)
        if n > 1:
            codes[1:n] = np.diff(v64[:n]).astype(np.uint64)
        base = np.asarray([v64[0] if n else 0], dtype=np.int64)
        return _pack_codes(codes, layout.width), mask_words, base
    else:
        codes = np.where(mask, vals.astype(np.int64) - np.int64(layout.ref),
                         0).astype(np.uint64)
    return _pack_codes(codes, layout.width), mask_words


def _unpack_codes(words, width: int, cap: int, xp):
    per = WORD_BITS // width
    w = xp.asarray(words)
    shifts = (xp.arange(per) * width).astype(np.uint32)
    m = np.uint32(0xFFFFFFFF) if width == WORD_BITS \
        else np.uint32((1 << width) - 1)
    # (per, n_words) planes, row axis minor-most — see _pack_codes
    codes = (w[None, :] >> shifts[:, None]) & m
    return codes.reshape(-1)[:cap]


def decode_slab(layout: ColLayout, slab, cap: int, xp):
    """One packed slab → (vals, mask) in the logical dtype. xp is numpy
    (CPU oracle) or jnp (traced inside the fragment — a gather-free
    broadcast shift/mask, plus one take for dict columns)."""
    validate(layout)
    words, mask_words = slab[0], slab[1]
    mask = _unpack_codes(mask_words, 1, cap, xp) != 0
    dt = layout.np_dtype
    if layout.width == 0:
        return xp.full(cap, layout.ref, dtype=dt), mask
    codes = _unpack_codes(words, layout.width, cap, xp)
    if layout.kind == "dict":
        # dict codes are < DICT_CARD_CAP, so int32 indexing is exact
        idx = xp.clip(codes.astype(np.int32), 0, layout.card - 1)
        return xp.take(xp.asarray(slab[2]), idx).astype(dt), mask
    if layout.kind == "delta":
        base = xp.asarray(slab[2]).astype(np.int64)[0]
        return _delta_values(layout, codes, base, cap, xp), mask
    return (codes.astype(np.int64) + np.int64(layout.ref)).astype(dt), mask


def delta_scan(layout: ColLayout, cap: int) -> str:
    """Which scan the TRACED decode of a `delta` column takes, from what is
    static in the trace (the layout and the slab's capacity):

      * "int32" — inside blocks of `DELTA_BLOCK` rows in 32 bits: a block's
        sum cannot pass 2^31 (`DELTA_BLOCK · (2^width − 1)`; every width
        up to 16), or the logical dtype has at most 32 bits itself, where
        wrapping sums are exact;
      * "wide" — blocked the same way, in 64 bits (an int64 column whose
        gaps need width 32);
      * "plain" — one 64-bit cumsum over the slab, as on the host: a
        capacity that a block does not divide, or of one block at most
        (toy slabs; the device cache's capacities are powers of two)."""
    if cap <= DELTA_BLOCK or cap % DELTA_BLOCK:
        return "plain"
    if layout.np_dtype.itemsize <= 4 or \
            DELTA_BLOCK * ((1 << layout.width) - 1) < 1 << 31:
        return "int32"
    return "wide"


def _delta_values(layout: ColLayout, codes, base, cap: int, xp):
    """`base + cumsum(codes)` in the logical dtype, exactly. A 64-bit
    cumsum over an 8M-row slab is an emulated-64-bit `reduce-window` to the
    TPU compiler: 9 ms a launched slab, two thirds of all device seconds of
    the benchmark's scans (PERF.md §6, PR 29). So a trace scans inside
    blocks of `DELTA_BLOCK` rows in the narrowest type that is exact there
    — log2(block) shifted adds, no reduce-window, no loop — scans the
    blocks' totals (cap / block of them) in the logical width, and widens
    each row once: 0.37–0.43 ms (1.5 ms where the blocks need 64 bits).
    The zero-padded tail holds the last value either way."""
    dt = layout.np_dtype
    scan = "plain" if xp is np else delta_scan(layout, cap)
    if scan == "plain":
        return (base + xp.cumsum(codes.astype(np.int64))).astype(dt)
    s = codes.astype(np.int32 if scan == "int32" else np.int64) \
        .reshape(-1, DELTA_BLOCK)
    k = 1
    while k < DELTA_BLOCK:
        s = s + xp.pad(s[:, :-k], ((0, 0), (k, 0)))
        k *= 2
    # values of at most 32 bits never need 64: sums that wrap are exact
    # modulo 2^32, and the true value fits the dtype
    top = np.int64 if dt.itemsize == 8 else np.int32
    totals = s[:, -1].astype(top)
    block_base = base.astype(top) + (xp.cumsum(totals) - totals)
    return (block_base[:, None] + s.astype(top)).reshape(-1).astype(dt)


def raw_slab_bytes(layout: ColLayout, cap: int) -> int:
    """Logical bytes one slab WOULD occupy uncompressed: values at the
    logical dtype plus the 1-byte-per-row bool validity mask."""
    return cap * (layout.np_dtype.itemsize + 1)


def packed_slab_bytes(layout: ColLayout, cap: int) -> int:
    """Physical bytes one packed slab occupies (words + mask words +
    the delta base), computable WITHOUT encoding it — the upload-bytes
    figure for slabs that zone-map pruning never encodes. Excludes the
    dict-layout dictionary array (uploaded once per column, not per
    slab)."""
    mask_bytes = 4 * (-(-cap // WORD_BITS))
    if layout.width == 0:
        return 4 + mask_bytes                 # the 1-word stub
    per = WORD_BITS // layout.width
    word_bytes = 4 * (-(-cap // per))
    base_bytes = 8 if layout.kind == "delta" else 0
    return word_bytes + mask_bytes + base_bytes


# ---------------------------------------------------------------------------
# Wide DECIMAL: limb planes
# ---------------------------------------------------------------------------

WIDE_LIMB_BITS = 30
WIDE_LIMB_BASE = 1 << WIDE_LIMB_BITS


def wide_decimal_limbs(vals, n_limbs: int) -> np.ndarray:
    """Arbitrary-precision scaled ints (object array) → (n_limbs, N) int64
    base-2³⁰ limb planes via shift/mask, so only the TOP limb is signed —
    value == Σ limbs[k]·2^(30k) exactly. The device-side layout of
    MyDecimal's word vector (types/mydecimal.go:236-246) as
    struct-of-arrays; ONE base everywhere (storage planes, on-device
    splits of narrow inputs, host recombination) so every producer/
    consumer pair agrees by construction."""
    out = np.empty((n_limbs, len(vals)), dtype=np.int64)
    cur = np.asarray(vals, dtype=object)
    mask = WIDE_LIMB_BASE - 1
    for k in range(n_limbs - 1):
        out[k] = (cur & mask).astype(np.int64)
        cur = cur >> WIDE_LIMB_BITS           # python ints: floor shift
    out[n_limbs - 1] = cur.astype(np.int64)   # top: small, carries sign
    return out


def wide_decimal_unlimb(limbs: np.ndarray) -> np.ndarray:
    """(n_limbs, G) int64 limb sums → object array of exact Python ints.
    Works on UNNORMALIZED limb sums (planes may exceed the base)."""
    n_limbs, g = limbs.shape
    out = np.zeros(g, dtype=object)
    for k in range(n_limbs - 1, -1, -1):
        out = out * WIDE_LIMB_BASE + limbs[k].astype(object)
    return out
