"""Session, planner: self time of the `parse` and `plan` lanes (parse,
planner.optimize with its rules, executor.build) and of `exec` (the session
driving the executor tree, outside the device fragment) per operation."""

import span_reduce


def read(ctx):
    return span_reduce.lanes_ms(ctx, ("parse", "plan", "exec"))
