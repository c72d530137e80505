"""A builder's read, not a benchmark cell: what the programs of ONE benchmark
run said of themselves WHILE THEY WERE TRACED.

Which lowering a traced program took rides its `launch` span as a tag
(`grouping` aside): `slot_sums` (PR 33/41), `delta_scan` (PR 29), `run_sums`
(PR 44), and `slab_pick` (PR 46: `index` on the first launch of a statement
program whose loop indexes the columns' stacked storage; a one-slab table's
says nothing). A benchmark window is warm and traces nothing, and the harness
switches the span recorder on for the window alone, so its traced run shows
none of them (`PERF.md` §3).

Runs `benchmarks/run.py` in this process with the same arguments, the span
recorder attached from the start (`timeline.capture`), and after its result
line prints one JSON line `traced_lowerings`: every `launch` span that
carries such a tag, in order — program name (`<kind>_<sig8>`, the
persistent compile cache's key) and its tags — and the always-on counters
that count the same — and `slab_stacks`: how many columns were stacked
(`slab.stack` spans, lane `cache`) and what their fills took, a set-up cost
no window shows. The recorder is on for the whole run: read no latency from
it.

    chiprun -- python3 tools/traced_lowerings.py --workload lgstream1.sf2 \\
        --seed <n> --seconds 40 --trace 1
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmarks")]

import run as bench                                           # noqa: E402

TAGS = ("run_sums", "slot_sums", "delta_scan", "slab_pick")
COUNTERS = ("tidb_tpu_run_sum_scans_total",
            "tidb_tpu_slot_sum_programs_total",
            "tidb_tpu_slot_sum_columns_total",
            "tidb_tpu_delta_decode_programs_total",
            "tidb_tpu_agg_partials_total",
            "tidb_tpu_statement_programs_total",
            "tidb_tpu_slab_stacks_total",
            "tidb_tpu_slab_slices_total")


def said(events) -> list:
    return [[e["name"], {t: e["args"][t] for t in TAGS if t in e["args"]}]
            for e in events
            if e.get("ph") == "X" and e.get("cat") == "launch"
            and any(t in e.get("args", {}) for t in TAGS)]


def stacks(events) -> dict:
    """The `slab.stack` spans: columns stacked, slabs moved, ms in all and
    the longest one."""
    spans = [e for e in events
             if e.get("ph") == "X" and e.get("name") == "slab.stack"]
    ms = [e["dur"] / 1e3 for e in spans]
    return {"columns": len(spans),
            "slabs": sum(e["args"].get("slabs", 0) for e in spans),
            "ms": round(sum(ms), 1), "max_ms": round(max(ms, default=0.0), 1)}


def main(argv=None) -> int:
    from tidb_tpu.util import timeline
    from tidb_tpu.util.observability import REGISTRY
    with timeline.capture() as cap:
        rc = bench.main(argv)
    counters = {f"{name}{{{','.join(f'{k}={v}' for k, v in labels)}}}": v
                for (name, labels), v in sorted(REGISTRY.counters.items())
                if name in COUNTERS}
    print(json.dumps({"phase": "traced_lowerings",
                      "launches": said(cap.events),
                      "slab_stacks": stacks(cap.events),
                      "counters": counters}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
