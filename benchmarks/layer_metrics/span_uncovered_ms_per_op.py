"""All host layers: self time of the `stmt` root span per operation — what,
between command received and last byte written, no child span covers."""

import span_reduce


def read(ctx):
    return span_reduce.lanes_ms(ctx, ("stmt",))
