"""Wire, session, planner, index: the CPU time one point read's thread RAN
inside the server — the sum of the self CPU (span field `cpu`) of the spans
of the traced window's point reads (`point_spans`' split: `stmt` root tagged
`class=interactive`, no `frag` span), per point read (`span_cpu.py`): the
share of `point_stmt_ms` that is work. None on a program whose spans carry
no `cpu`."""

import span_cpu


def read(ctx):
    return span_cpu.ms(ctx, "points",
                       lambda got: got["terms"].get("cpu", 0.0))
