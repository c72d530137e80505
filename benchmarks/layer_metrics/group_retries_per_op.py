"""Fragments: capacity-ladder retries per operation — `ladder.retry` spans
(lane `frag`, tags `rung`, `need`) over the traced window. 0 in a warm
window: the specialization cache adopts the capacities the first run
settled on."""

import span_events
import span_reduce


def read(ctx):
    got = span_reduce.window(ctx)
    # the fragment spans of this program say whether it records retries at
    # all: one that does not tag `groups` has no `ladder.retry` either
    tagged = any(span_events.tag(f, "groups", None) is not None
                 for f in span_events.spans(ctx, "frag", "device.fragment"))
    if got is None or not got["ops"] or not tagged:
        return None
    return len(span_events.spans(ctx, "frag", "ladder.retry")) / got["ops"]
