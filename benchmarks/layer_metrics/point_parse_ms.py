"""Wire, parser: self time of lane `parse` (`parse_with_text` over the
statement text with the parameters substituted) per point read of the traced
window (`point_spans.py`): what lexing and parsing again costs an EXECUTE of
a statement that was prepared once."""

import point_spans


def read(ctx):
    return point_spans.per_point(
        ctx, lambda got: got["point_self_s"].get("parse", 0.0) * 1e3)
