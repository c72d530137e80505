"""Scheduler: how long the device's one batch slot is HELD — the duration
of the `sched-slot` spans (lane `sched`) of the traced window's operations,
per operation (`span_cpu.py`): the scheduler's "time busy", while every
other statement queues (`sched-queue*`). The `span_cpu` line splits the hold
into its CPU, device-wait and lock-wait parts by the spans' `cpu` field.
None on a program whose spans carry no `cpu`."""

import span_cpu


def read(ctx):
    return span_cpu.ms(ctx, "ops", lambda got: got["slot"]["hold_s"])
