"""Device cache, compaction: self time of the compactor's spans
(`compact.run`, `compact.warm`, `compact.swap`, lane `delta`; on the compactor's
own thread, in the scheduler's batch class) per operation of the traced
window (`write_spans.py`), and on a printed `compactions` line the window's
count of committed compactions (`tidb_tpu_compactions_total`, sampled by
the operation kind). 0 where none ran."""

import json

import refresh_counters
import write_spans


def read(ctx):
    ms = write_spans.ms_per_operation(ctx, "compact_s")
    if ms is not None:
        print(json.dumps({
            "phase": "compactions",
            "committed_in_window": refresh_counters.window_delta(
                ctx, "compactions"),
            "self_ms_per_operation": ms}), flush=True)
    return ms
