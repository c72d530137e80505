"""All host layers: the CPU time the statements' threads RAN — the sum of
the self CPU (span field `cpu`, `time.thread_time_ns` at entry and exit) of
every span of the traced window's operations, per operation
(`span_cpu.py`). The work a statement costs the host, whatever it waited
for. None on a program whose spans carry no `cpu`."""

import span_cpu


def read(ctx):
    return span_cpu.ms(ctx, "ops", lambda got: got["terms"].get("cpu", 0.0))
