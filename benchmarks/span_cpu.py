"""Waiting told from working: the traced window's spans split into the CPU
time their thread ran, the waits the program designed, and what is left.

Since PR 38 every span the program records (`tidb_tpu/util/timeline.py`)
carries `cpu`, the microseconds its THREAD ran between entry and exit, and a
span that blocks by design carries `wait=<kind>` (`device`, `queue`,
`socket`, `build`, `lock`). A span's parent is the enclosing span of the same
thread, so on one clock

  self CPU      = its `cpu` less its children's;
  self off-CPU  = its self wall time (`span_reduce.self_times`) less its
                  self CPU: the thread was not running — it waited for a
                  lock, blocked in a call, or had handed the work to
                  another thread.

and a request's `stmt` root = Σ self CPU (`cpu`: work) + Σ self off-CPU of
`wait`-tagged events by kind (`wait`) + Σ self off-CPU of untagged spans
(`lock_wait`: nothing in the program designed it — the interpreter's lock,
plus the operating system's run-queue delay) + `unclocked` (durations
measured elsewhere, `timeline.record`, with neither `cpu` nor `wait`:
`gc.gen2`, `jax.trace`; nothing in a warm window).

Neither is floored or capped span by span: the sums are what is read, and
they telescope (Σ self CPU of a request = its root's `cpu`). The thread
clock is the kernel's, and a kernel may tick it coarsely — the chip hosts'
does, 10 ms a step (`PERF.md` §6, PR 38) — so that ONE span's `cpu` reads 0
or a whole step whatever it ran; a tick lands in a span as often as the
thread runs there, so sums over a window stay right (±1/√ticks), and a
floor or cap on each span would bend them. `cpu_step_us` in the line is the
smallest non-zero `cpu` the window holds: the step as observed, and so how
small a sum still means something (a term of n steps is good to 1/√n).

`window(ctx)` reduces the window's OPERATIONS (requests that ran a device
fragment, as `span_reduce` keeps them) and, where the `stmt` roots carry a
`class` tag, its POINT READS (`point_spans`' split: `class=interactive`, no
`frag` span), prints ONE `{"phase": "span_cpu", ...}` line (ms per
operation: the terms, the same by lane and for the 16 largest spans, and the
`sched-slot` holds' whole duration with its CPU, device-wait and lock-wait
parts) and hands the numbers to the six readers in `layer_metrics/`. On a
program whose spans carry no `cpu` it returns None and prints nothing.
"""

from __future__ import annotations

import json

import span_events
import span_reduce

SLOT = ("sched", "sched-slot")      # lane, name prefix: a batch-slot hold
LOCK_LANE = "lock"                  # lock.wait: the program's own locks


def _args(e) -> dict:
    return e.get("args") or {}


def split(events) -> list:
    """→ [(event, term, self CPU seconds, self off-CPU seconds)] for every
    "X" event; `term` names where its off-CPU time is booked: the `wait`
    kind of a tagged event, "lock_wait" for an untagged span, "unclocked"
    for an untagged duration without `cpu`. Not floored (module
    docstring): under a coarse clock one span's CPU may exceed its wall
    time and its off-CPU read negative; the sums are what counts."""
    kids_cpu: dict = {}
    for e in events:
        a = _args(e)
        if "cpu" in a and a.get("parent"):
            kids_cpu[a["parent"]] = kids_cpu.get(a["parent"], 0.0) + a["cpu"]
    out = []
    for e, wall in span_reduce.self_times(events):
        a = _args(e)
        cpu = 0.0
        if "cpu" in a:
            cpu = (a["cpu"] - kids_cpu.get(a.get("id"), 0.0)) * 1e-6
        term = a.get("wait") or ("lock_wait" if "cpu" in a else "unclocked")
        out.append((e, term, cpu, wall - cpu))
    return out


def _add(into: dict, term: str, cpu: float, off: float) -> None:
    into["cpu"] = into.get("cpu", 0.0) + cpu
    into[term] = into.get(term, 0.0) + off


def account(parts, reqs, roots) -> dict:
    """The three terms over the events of requests `reqs` (seconds):
    {"n", "stmt_s", "terms" {cpu, lock_wait, unclocked, <wait kind>...},
    "by_lane" {lane: terms}, "by_name" {lane/name: terms}, "slot"
    {holds, hold_s, cpu_s, terms of the holds' subtrees}}."""
    terms: dict = {}
    by_lane: dict = {}
    by_name: dict = {}
    own: dict = {}          # span id → (term, cpu, off), for the subtrees
    below: dict = {}        # span id → its children's ids and leaves
    slots = []
    for e, term, cpu, off in parts:
        a = _args(e)
        if a.get("req", 0) not in reqs:
            continue
        _add(terms, term, cpu, off)
        _add(by_lane.setdefault(e["cat"], {}), term, cpu, off)
        _add(by_name.setdefault(f"{e['cat']}/{e['name']}", {}),
             term, cpu, off)
        key = a.get("id") or id(e)      # a leaf measured elsewhere has none
        own[key] = (term, cpu, off)
        if a.get("parent"):
            below.setdefault(a["parent"], []).append(key)
        if e["cat"] == SLOT[0] and e["name"].startswith(SLOT[1]):
            slots.append(e)
    slot: dict = {"holds": len(slots),
                  "hold_s": sum(e.get("dur", 0.0) for e in slots) * 1e-6,
                  "terms": {}}
    for e in slots:
        todo = [_args(e)["id"]]
        while todo:
            key = todo.pop()
            _add(slot["terms"], *own[key])
            todo += below.get(key, ())
    return {"n": len(roots),
            "stmt_s": sum(e.get("dur", 0.0) for e in roots) * 1e-6,
            "terms": terms, "by_lane": by_lane, "by_name": by_name,
            "slot": slot}


def reduce(events) -> dict | None:
    """→ {"ops": account of the operations, "points": account of the point
    reads or None, "cpu_step_us": the smallest non-zero `cpu` of any span};
    None when no span of an operation carries `cpu`."""
    spans = [e for e in events if e.get("ph") == "X"]
    req = lambda e: _args(e).get("req", 0)  # noqa: E731
    on_device = {req(e) for e in spans
                 if e["cat"] == span_reduce.FRAGMENT_LANE} - {0}
    roots = [e for e in spans if e["cat"] == span_reduce.ROOT_LANE]
    op_roots = [e for e in roots if req(e) in on_device]
    if not op_roots or not any("cpu" in _args(e) for e in op_roots):
        return None
    # a request the recorder met half-way has no root to close on
    on_device = {req(e) for e in op_roots}
    parts = split(spans)
    # point_spans' split: interactive requests that ran no fragment
    point_roots = [e for e in roots
                   if _args(e).get("class") == "interactive"
                   and req(e) and req(e) not in on_device]
    ran = [_args(e)["cpu"] for e in spans if _args(e).get("cpu")]
    return {"ops": account(parts, on_device, op_roots),
            "points": account(parts, {req(e) for e in point_roots},
                              point_roots) if point_roots else None,
            "cpu_step_us": min(ran) if ran else None}


def _per(terms: dict, n: int) -> dict:
    """Seconds by term → ms per request: `cpu`, `lock_wait`, `wait` by
    kind (and `unclocked` where there is any)."""
    out = {"cpu": terms.get("cpu", 0.0) / n * 1e3,
           "lock_wait": terms.get("lock_wait", 0.0) / n * 1e3,
           "wait": {k: v / n * 1e3 for k, v in sorted(terms.items())
                    if k not in ("cpu", "lock_wait", "unclocked")}}
    if terms.get("unclocked"):
        out["unclocked"] = terms["unclocked"] / n * 1e3
    return out


def _line(got: dict) -> dict:
    n = got["n"]
    slot = got["slot"]
    largest = sorted(got["by_name"].items(),
                     key=lambda kv: -sum(kv[1].values()))[:16]
    return {"requests": n, "stmt_ms": got["stmt_s"] / n * 1e3,
            **_per(got["terms"], n),
            "sum_over_stmt": (sum(got["terms"].values()) / got["stmt_s"]
                              if got["stmt_s"] else None),
            "by_lane": {k: _per(v, n)
                        for k, v in sorted(got["by_lane"].items())},
            "by_span": {k: _per(v, n) for k, v in largest},
            "sched_slot": {"holds": slot["holds"],
                           "hold_ms": slot["hold_s"] / n * 1e3,
                           **_per(slot["terms"], n)}}


def window(ctx) -> dict | None:
    """The traced run's spans, split once and printed once a run (kept on
    the run's own `ctx`, which every reader is handed)."""
    if "_span_cpu" in ctx:
        return ctx["_span_cpu"]
    got = None
    try:
        got = reduce(span_events.events(ctx))
        if got is not None:
            print(json.dumps({
                "phase": "span_cpu", "cpu_step_us": got["cpu_step_us"],
                "per_op": _line(got["ops"]),
                "per_point_read": (_line(got["points"])
                                   if got["points"] else None)}),
                flush=True)
    except Exception as e:  # noqa: BLE001 — a reader never sinks the run
        got = None
        print(json.dumps({"phase": "span_cpu", "error": repr(e)}),
              flush=True)
    ctx["_span_cpu"] = got
    return got


def ms(ctx, who: str, value):
    """`value(account)` seconds ÷ the requests of `who` ("ops" or
    "points"), in ms; None where there is nothing to read."""
    got = window(ctx)
    if got is None or not got[who] or not got[who]["n"]:
        return None
    return value(got[who]) / got[who]["n"] * 1e3
