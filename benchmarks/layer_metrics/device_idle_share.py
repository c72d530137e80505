"""Device: share of the traced span in which no operation ran on the chip
(1 − union of the device-op intervals ÷ span, mean over chips), from the
profiler's trace. Percent."""


def read(ctx):
    tr = ctx["trace"]
    return 100.0 * tr["idle_share"] if tr else None
