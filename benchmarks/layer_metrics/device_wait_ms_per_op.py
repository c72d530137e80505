"""Device, as the host sees it: seconds inside `block_until_ready` (the
`drain` spans) per operation. Under several connections it holds the other
statements' device time too."""

import span_reduce


def read(ctx):
    return span_reduce.lanes_ms(ctx, ("drain",))
