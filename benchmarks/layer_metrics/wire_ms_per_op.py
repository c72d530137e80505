"""Wire: self time of the server's `wire.read` (decode, placeholder
substitution) and `wire.write` (row encoding and send) spans per operation,
from the program's timeline over the traced window."""

import span_reduce


def read(ctx):
    return span_reduce.lanes_ms(ctx, ("wire",))
