"""The bytes the window's tombstone rewrites had to move, worked out by the
benchmark from what the program's spans say it rewrote.

A deleted row is one bit of its slab's liveness mask cleared, and a
generation is immutable (readers of the old one keep it), so a rewrite reads
the slab's mask once and writes the new one once, at the least:

    rows x MASK_BYTES_PER_ROW x 2

summed over the `delta.tombstone` spans (lane `delta`; `rows` = the
rewritten slab's capacity, `tombs` = the rows that died in it). No column of
the slab is touched, whatever its layout: the bytes a row of the rewritten
part holds is the mask's one. It is the numerator of `tombstone_hbm_share`.
"""

from __future__ import annotations

import span_events

MASK_BYTES_PER_ROW = 1          # a bool a row


def moved_bytes(tombstone_spans) -> int:
    return 2 * MASK_BYTES_PER_ROW * int(sum(
        span_events.tag(e, "rows") for e in tombstone_spans))


def window_bytes(ctx):
    spans = span_events.spans(ctx, "delta", "delta.tombstone")
    if not spans:
        return None
    return moved_bytes(spans)
