"""Device cache, generations: per operation, the window's cached reads that
were answered by a table REBUILT beside the cache
(`tidb_tpu_delta_generation_reads_total{age=rebuilt}`: a snapshot older than
every generation kept) plus every decline the window counted
(`tidb_tpu_delta_declines_total`, all gates: a full rebuild each), sampled
by the operation kind (`generation_counters.py`), with a printed
`stale_rebuilds` line that says which. Must read 0: one rebuild of lineitem
at SF=4 is a minute inside a statement. None on a program that does not
count reads by age."""

import json

import generation_counters


def read(ctx):
    reads = generation_counters.window_delta(ctx, "reads")
    fell = generation_counters.window_delta(ctx, "declines")
    if reads is None or fell is None:
        return None
    fell = {g: n for g, n in fell.items() if n}
    print(json.dumps({"phase": "stale_rebuilds",
                      "reads_by_age": reads, "declines_by_gate": fell}),
          flush=True)
    return (reads.get("rebuilt", 0.0) + sum(fell.values())) \
        / ctx["attempted"]
