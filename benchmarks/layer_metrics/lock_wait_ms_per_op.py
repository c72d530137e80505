"""All host layers: the time the statements' threads did NOT run and no
site says why — the sum of the self off-CPU time (self wall time less self
CPU, span field `cpu`) of the operations' spans WITHOUT a `wait` tag, per
operation (`span_cpu.py`): the wait for the interpreter's lock (plus the
operating system's run-queue delay). None on a program whose spans carry no
`cpu`."""

import span_cpu


def read(ctx):
    return span_cpu.ms(ctx, "ops",
                       lambda got: got["terms"].get("lock_wait", 0.0))
