"""The bytes a grouped aggregation has to move, worked out by the benchmark
from what the program's spans say it launched.

A grouping reads every row's key and state contribution once and writes
them once, at the least: a sort-based one moves them several times (each
sort pass), a scatter-based one less regularly. So the least is

    rows × (key_bytes + state_bytes) × 2

summed over the launched partials (`device.fragment` spans: `rows_in` rows
of the launched slabs) and the merges (`frag.merge` spans: `slots_in`
partial slots), with `key_bytes` and `state_bytes` the widths of one
group's keys and aggregate states as the span states them. It is the
numerator of `group_hbm_share`; a share near 100% would mean the grouping
runs at the HBM bound.
"""

from __future__ import annotations

import span_events


def moved_bytes(fragment_spans, merge_spans) -> int:
    """`device.fragment` and `frag.merge` events (dicts with `args`) → the
    least bytes their groupings moved. A fragment that grouped nothing
    (no `key_bytes` tag) counts nothing; a merge takes the widths of the
    fragment span it ran under (`args.parent`)."""
    tag = span_events.tag
    width = {tag(f, "id"): tag(f, "key_bytes") + tag(f, "state_bytes")
             for f in fragment_spans}
    total = sum(tag(f, "rows_in") * width[tag(f, "id")]
                for f in fragment_spans)
    total += sum(tag(m, "slots_in") * width.get(tag(m, "parent"), 0)
                 for m in merge_spans)
    return 2 * int(total)


def window_bytes(ctx):
    """The traced window's grouping bytes, or None when no span carries
    the tags (a program that has none)."""
    frags = [f for f in span_events.spans(ctx, "frag", "device.fragment")
             if span_events.tag(f, "key_bytes", None) is not None]
    if not frags:
        return None
    return moved_bytes(frags, span_events.spans(ctx, "frag", "frag.merge"))
