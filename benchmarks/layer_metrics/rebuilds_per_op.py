"""Device cache, delta generations: full rebuilds a stale cached table fell
to, per operation — the window's delta of the always-on counter
`tidb_tpu_delta_declines_total` (every gate), sampled by the operation kind
(`refresh_counters.py`). 0 where every write extended."""

import refresh_counters


def read(ctx):
    fell = refresh_counters.window_delta(ctx, "declines")
    return None if fell is None else fell / ctx["attempted"]
