"""Device cache, fragments, store, index: self time of lane `lock` —
`lock.wait` spans (`name=<lock>`, `wait=lock`), recorded around a CONTENDED
acquire of one of the program's own locks (`timeline.named_lock`) — per
operation (`span_cpu.py`). 0 where no acquire was contended; None on a
program whose spans carry no `cpu` (it has no such lane either)."""

import span_cpu


def read(ctx):
    return span_cpu.ms(
        ctx, "ops",
        lambda got: sum(got["by_lane"].get(span_cpu.LOCK_LANE, {}).values()))
