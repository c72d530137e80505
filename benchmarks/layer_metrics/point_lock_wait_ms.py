"""Wire, session, planner, index: the time one point read's thread did NOT
run and no site says why — the sum of the self off-CPU time of its spans
WITHOUT a `wait` tag, per point read (`span_cpu.py`): the share of
`point_stmt_ms` that is the wait for the interpreter's lock. None on a
program whose spans carry no `cpu`."""

import span_cpu


def read(ctx):
    return span_cpu.ms(ctx, "points",
                       lambda got: got["terms"].get("lock_wait", 0.0))
