"""Span tracing — the TRACE statement's recorder.

The reference threads OpenTracing spans through every layer (SURVEY §5:
per-executor spans in the Next wrapper executor/executor.go:278, session
compile spans session.go:1615) and renders them with `TRACE SELECT …`
(executor/trace.go). This module is the in-process equivalent: a
zero-dependency span tree with microsecond offsets, attached to the
session only while a TRACE statement runs (no overhead otherwise), plus
the optimizer-trace hook (util/tracing/opt_trace.go analog) that records
which rewrite rules fired.

`maybe_span` is also a recording site of the process-wide timeline
(util/timeline.py): the boundaries that exist for TRACE — planner.optimize,
optimize.logical/physical, rule.*, executor.build, executor.run,
device.fragment — record there under the same names whenever the timeline
is on, TRACE or not, and TRACE's own rows do not change."""

from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

from tidb_tpu.util import timeline


class Span:
    __slots__ = ("name", "start", "end", "children", "tags")

    def __init__(self, name: str, start: float):
        self.name = name
        self.start = start
        self.end = start
        self.children: List["Span"] = []
        self.tags: Dict[str, object] = {}


class Tracer:
    def __init__(self):
        self._t0 = time.perf_counter()
        self.root = Span("trace", 0.0)
        self._stack: List[Span] = [self.root]

    def _now(self) -> float:
        return time.perf_counter() - self._t0

    @contextmanager
    def span(self, name: str, **tags):
        s = Span(name, self._now())
        s.tags.update(tags)
        self._stack[-1].children.append(s)
        self._stack.append(s)
        try:
            yield s
        finally:
            s.end = self._now()
            self._stack.pop()

    def event(self, name: str, duration_s: float = 0.0, **tags) -> None:
        """A leaf span with an externally measured duration (e.g. an
        operator's accumulated wall time from runtime stats)."""
        now = self._now()
        s = Span(name, max(now - duration_s, 0.0))
        s.end = now
        s.tags.update(tags)
        self._stack[-1].children.append(s)

    def rows(self) -> List[Tuple[str, str, str]]:
        """(operation, startTs µs, duration µs) rows, depth-indented —
        the executor/trace.go row shape."""
        out: List[Tuple[str, str, str]] = []

        def rec(s: Span, depth: int):
            label = "  " * depth + ("└─" if depth else "") + s.name
            if s.tags:
                label += " " + ", ".join(f"{k}={v}"
                                         for k, v in sorted(s.tags.items()))
            out.append((label, f"{s.start * 1e6:.0f}",
                        f"{(s.end - s.start) * 1e6:.0f}"))
            for c in s.children:
                rec(c, depth + 1)

        self.root.end = self._now()
        rec(self.root, 0)
        return out


def _lane(name: str) -> str:
    """The timeline lane of a TRACE site: planning and executor build are
    `plan`, one device fragment's whole run is `frag` (its self time is
    fragment set-up), running the executor tree is `exec`."""
    if name == "device.fragment":
        return "frag"
    head = name.split(".", 1)[0]
    return "plan" if head in ("planner", "optimize", "rule") \
        or name == "executor.build" else "exec"


@contextmanager
def maybe_span(tracer: Optional[Tracer], name: str, **tags):
    with timeline.span(name, _lane(name), **tags):
        if tracer is None:
            yield None
        else:
            with tracer.span(name, **tags) as s:
                yield s
