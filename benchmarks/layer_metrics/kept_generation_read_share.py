"""Device cache, generations: percent of the window's cached table reads
that were served from a generation KEPT behind the key's newest one — a
statement whose snapshot a commit and another connection's read had
overtaken — out of the always-on counter
`tidb_tpu_delta_generation_reads_total{age=newest|kept|rebuilt}`, sampled by
the operation kind (`generation_counters.py`). None on a program that does
not count reads by age."""

import generation_counters


def read(ctx):
    reads = generation_counters.window_delta(ctx, "reads")
    if reads is None or not sum(reads.values()):
        return None
    return 100.0 * reads.get("kept", 0.0) / sum(reads.values())
