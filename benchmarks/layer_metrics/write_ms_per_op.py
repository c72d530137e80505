"""Session, store: self time of lane `write` per operation — `write.stage`
(a DML statement from parse to staged rows or matched masks) and
`write.commit` (`Store.commit`) — from the program's timeline over the
traced window (`write_spans.py`). None on a program without the lane."""

import write_spans


def read(ctx):
    return write_spans.ms_per_operation(ctx, "write_s")
