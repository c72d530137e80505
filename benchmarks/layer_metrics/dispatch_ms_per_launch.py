"""Fragments: what the host spends to get one program onto the device. Self
time of the `frag` lane (a device fragment's run less its launches, waits,
fetches and decodes: signature and specialization lookup, slab pruning, warm
open_table, argument assembly, the eager glue between launches) plus the
`launch` spans themselves, per `launch` span."""

import span_reduce


def read(ctx):
    return span_reduce.lanes_ms(ctx, ("frag", "launch"), per="launches")
