"""Device cache, first touch: host seconds of the encode stages, from the
program's always-on counters `tidb_tpu_first_touch_seconds_total{stage=}`
(materialize, layout, dict, pack; `upload` is the transfer and is left out of
the sum). First touch happens in set-up, before the timeline is on. Prints
the seconds by stage, `upload` too, as one `first_touch_stages` line."""

import json

STAGES = ("materialize", "layout", "dict", "pack")
COUNTER = "tidb_tpu_first_touch_seconds_total"


def read(ctx):
    from tidb_tpu.util.observability import REGISTRY
    by_stage = {labels.partition("=")[2]: v
                for name, labels, v in REGISTRY.metric_rows()
                if name == COUNTER}
    got = [by_stage[k] for k in STAGES if k in by_stage]
    if not got:
        return None
    print(json.dumps({"phase": "first_touch_stages", "seconds": by_stage}),
          flush=True)
    return sum(got)
