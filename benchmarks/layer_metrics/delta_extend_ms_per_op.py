"""Device cache, delta generations: what a write costs the next read of
each cached table, per operation — self time of lane `delta` without the
compactor's spans (`delta.diff`, `delta.encode`, `delta.upload`,
`delta.tombstone`, `delta.aligned`), from the program's timeline over the
traced window (`write_spans.py`). None on a program without the lane."""

import write_spans


def read(ctx):
    return write_spans.ms_per_operation(ctx, "delta_s")
