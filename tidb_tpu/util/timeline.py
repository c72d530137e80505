"""Process-wide span recorder: one Chrome-trace timeline from packet-in to
last byte out (ref: util/tracecpu + the TopSQL collector; rendering targets
chrome://tracing / Perfetto).

ONE recording site, `span(name, lane, **args)`, serves three renderers:

  * the cross-session Chrome JSON (`SET tidb_tpu_trace_dir = '/path'`:
    <dir>/tidb_tpu_trace_<os-pid>.json, written every 5 s from the
    statement path, on `flush()` and on `stop_global()` — NOT after every
    statement. An event is rendered ONCE: the statement path renders
    what is pending a batch at a time (`RENDER_BATCH` events, in `ts`
    order within the batch), so a statement pays for about its own events
    and none for a window's; a write renders what is left, and the file
    write — the whole file again, in pieces of `WRITE_PIECE` bytes,
    which releases the interpreter's lock — carries the rest. The
    stopped collector's events stay readable through `last_events()`
    until the next `start_global`);
  * `TRACE FORMAT='chrome' <stmt>`: a scoped collector for one statement,
    returned as a result row (executor/trace.go's chrome format analog);
  * the `jax.profiler` trace: while a collector is attached every span also
    enters a `jax.profiler.TraceAnnotation` named `tidb_tpu/<lane>/<name>`
    carrying `req` and `conn`, so a profile taken while the timeline is on
    holds the program's spans on the profiler's own clock beside the
    device lines, in one file. A request's root span also carries the
    timeline's own `ts_us`, which ties the two clocks together.
  `TRACE <stmt>`'s rows (util/tracing.py) go through the same sites:
  `maybe_span` records here under the same names.

An event is a Chrome "X" event:

  * pid  = connection id (one process lane per session),
  * tid  = lane (`STREAMS`), named via thread_name metadata,
  * ts   = microseconds on one shared monotonic epoch, so cross-thread
           ordering in the viewer is real ordering,
  * args = `req` (the request id: a process-wide counter minted where the
           request enters — the server's command loop, or Session.execute
           when no server is above it; the statements of one command share
           it), `id` / `parent` (the enclosing span on the same thread),
           `cause` (the request that did the work this one waited for: a
           micro-batch leader's launch), `cpu` and `wait` (below) and the
           site's own tags.

Waiting told from working. A span's wall time is work plus waiting, and
under one interpreter lock most of a busy server's wall time is waiting:

  * `cpu`: microseconds THIS THREAD ran between the span's entry and its
    exit (`time.thread_time_ns`, read beside the wall clock). A span's
    parent is the enclosing span of the same thread, so on one clock a
    span's *self CPU* = its `cpu` less its children's, and its *self
    off-CPU* = its self wall time less its self CPU. Off-CPU means the
    thread was not running: it waited for a lock, blocked in a call, or
    handed the work to another thread. It does not say which. Events
    measured elsewhere (`record`: `sched-queue*`, `jax.*`, `gc.gen2`)
    carry no `cpu`. The clock is the kernel's, and so are its step and
    its price: where the kernel ticks thread time coarsely (a sandboxed
    kernel may, 10 ms a step) ONE span's `cpu` reads 0 or a whole step
    whatever it ran, and only sums over many spans mean anything — a
    reducer must not floor or cap span by span; where the read is a real
    system call it is the dearest thing a span does.
  * `wait=<kind>`: a span that blocks BY DESIGN says so at its site —
    `device` (`drain`; `fetch`, whose `device_get` waits for the
    transfer), `queue` (`sched-queue*`, `microbatch.wait`), `socket`
    (`client.wait`), `build` (`compile.wait`, `compile:*`,
    `jax.backend_compile`: the work runs on XLA's threads), `lock`
    (lane `lock`: `lock.wait`, one of the program's own locks, and
    `commit.gate`, a COMMIT waiting for the store's lock while a snapshot
    is taken or another commit applies).
  * so a request's account closes: its `stmt` root = the sum of self CPU
    (work) + the off-CPU self time of `wait`-tagged spans by kind + the
    off-CPU self time of untagged spans. The last term is what nothing
    in the program designed: the wait for the interpreter's lock, plus
    the operating system's run-queue delay — off-CPU cannot tell those
    two apart (`/proc/<pid>/task/*/schedstat` can), nor a native call
    that blocks without a tag (tag it). `benchmarks/span_cpu.py` is the
    reducer.

Lanes, from packet to packet:

  client   server blocked in read_packet for the next command
  stmt     root, one per request: command received -> last result byte
           (proto=text|binary under the wire server; class=interactive|
           batch|none, what the session's admission classifier decided;
           sql=<first 80 characters>)
  wire     wire.read (decode, placeholder substitution; params=<n> for a
           COM_STMT_EXECUTE), wire.write (row encoding, framing into the
           connection's buffer and the send: sends=<socket calls>,
           packets=<packets they carried> — one send a response unless
           it outgrows the buffer). Always on beside them:
           tidb_tpu_wire_socket_calls_total{kind=send|recv} and
           tidb_tpu_wire_packets_total{kind=send|recv}, counted once a
           flush and once a burst read, never once a packet
  parse    parse_with_text
  plan     planner.optimize (cache=hit|miss), optimize.*, rule.*,
           executor.build
  exec     executor.run (the TRACE site)
  frag     device.fragment (the TRACE site, spec=hit|miss; over an
           aggregate grouping=global|bounds|runs|factorize, gcap=<slots>): one
           per device fragment; its SELF time is fragment set-up — signature /
           specialization lookup, prune_slabs, argument assembly,
           everything between launches — beside its children frag.open
           (warm open_table), frag.program (program lookup) and frag.glue
           (eager stacking of slab partials)
  sched    admission queue waits and slot holds
  compile  cold program builds (compile:<kind>), JAX's trace / lower /
           backend-compile durations, waits for another request's build
  encode   first touch: encode.materialize / .layout / .dict / .pack
  upload   host -> device transfers
  launch   one per jitted call, program=<kind>_<sig8>, slab=<i>; the call
           that traces a program over a `delta` column also carries
           delta_scan=int32|wide|plain (device_emit.emit_decode)
  drain    block_until_ready waits
  fetch    device -> host result transfers
  decode   host-side dictionary decode / Chunk assembly
  cache    evictions (instants)
  write    write.stage (a DML statement's rows staged or matched),
           write.commit (Store.commit: tables, rows, tombs; a CONTENDED
           acquire of the store's lock under it is `commit.gate`, lane
           `lock` like every wait for a program lock)
  delta    what a write costs the next read of a cached table
           (executor/delta.py): delta.diff, delta.encode, delta.upload,
           delta.tombstone (one per slab whose liveness mask changed),
           delta.aligned (the FK-aligned joins following a generation),
           delta.decline (instant, gate=), compact.run, compact.swap;
           delta.generation (age=newest|kept|rebuilt) around the look-up
           of a snapshot's generation where the key's entry is another
           snapshot's (device_cache.open_table: the kept generation, the
           extension, or the rebuild beside; always on beside it,
           tidb_tpu_delta_generation_reads_total{age=} counts EVERY
           cached read, and the gauges tidb_tpu_delta_generations_kept
           and ..._kept_bytes what is kept behind the newest ones)
  index    the host's index access path (executor/index_scan.py):
           index.build (table, index, rows: once a table version — the
           live view gathered and its key sorted), index.probe (ranges,
           rows out: binary searches, gather, residual filters)
  gc       generation-2 garbage collections, while the global collector
           is attached
  lock     lock.wait (name=<lock>, wait=lock): a CONTENDED acquire of one
           of the program's own locks on the statement path
           (`named_lock`: the device cache, the compiled-program cache,
           the store, the index views, the metrics registry, ...); an
           uncontended acquire records nothing

Opt-in and zero-cost when off: `span()` returns one shared no-op object
when `ENABLED` is false (no event, no TraceAnnotation, no clock read),
`record()`/`instant()`/`tag()` return at once, and a `named_lock` does its
plain acquire.
"""

from __future__ import annotations

import gc
import itertools
import json
import operator
import os
import threading
import time
from contextlib import contextmanager
from typing import Dict, List, Optional

# Fast-path flag: True iff at least one collector is attached.  Recording
# sites read this before building any event dict, so tracing-off overhead
# is a single module-attribute load.
ENABLED = False

# re-entrant: a generation-2 collection can start (and its callback record)
# while this thread copies the event list under the lock
_LOCK = threading.RLock()
_T0 = time.perf_counter()          # shared epoch for every thread's ts

# lanes: stable small tids so the viewer groups events the same way run
# over run; thread_name metadata labels them at flush
STREAMS = {"sched": 1, "compile": 2, "encode": 3, "upload": 4,
           "fetch": 6, "decode": 7, "cache": 8,
           # staged-exchange per-rank stage lanes: partition (stage 1),
           # checkpoint (stage 2 device→host + host routing), probe
           # (stage 3 receive/probe/dedup)
           "partition": 9, "checkpoint": 10, "probe": 11,
           # packet to packet (module docstring)
           "client": 12, "stmt": 13, "wire": 14, "parse": 15, "plan": 16,
           "exec": 17, "frag": 18, "launch": 19, "drain": 20, "gc": 21,
           "write": 22, "delta": 23, "index": 24, "lock": 25}
_OTHER_TID = 31

_GLOBAL: Optional["_Collector"] = None     # tidb_tpu_trace_dir sink
_GLOBAL_PATH: Optional[str] = None
_LAST: List[dict] = []                     # the stopped global's events
_SCOPED: List["_Collector"] = []           # TRACE FORMAT='chrome' sinks
_NEXT_FLUSH = 0.0                          # time.monotonic() of the next
FLUSH_INTERVAL_S = 5.0                     # statement-path write
RENDER_BATCH = 512                         # events the statement path
                                           # renders at once
WRITE_PIECE = 1 << 23                      # bytes a `write` of the file
_FLUSH_LOCK = threading.Lock()             # one writer of the file at a time

_REQUEST_IDS = itertools.count(1)
_SPAN_IDS = itertools.count(1)
_tls = threading.local()
_ANNOTATION = None      # jax.profiler.TraceAnnotation, bound on first use


class _Collector:
    __slots__ = ("events", "dirty", "rendered", "chunks", "lanes")

    def __init__(self):
        self.events: List[dict] = []
        self.dirty = False
        # the global collector's file, kept as rendered: how many of
        # `events` the chunks hold, one chunk of JSON text per write, and
        # the (pid, tid) → lane pairs the metadata names
        self.rendered = 0
        self.chunks: List[bytes] = []
        self.lanes: Dict[tuple, str] = {}


# JAX's own duration events → `compile`-lane spans: a program is traced,
# lowered and compiled inside its first launch, not where it is built (but
# for a statement program, which compiles where it is built, outside the
# batch slot: `agg_slabs._StatementProgram`)
_JAX_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "jax.trace",
               "/jax/core/compile/jaxpr_to_mlir_module_duration":
                   "jax.lower",
               "/jax/core/compile/backend_compile_duration":
                   "jax.backend_compile"}


def _on_jax_duration(event: str, duration_secs: float, **_kw) -> None:
    name = _JAX_EVENTS.get(event) if ENABLED else None
    if name is not None:
        # the backend's compile runs on XLA's threads: a designed wait
        record(name, "compile", dur_us=duration_secs * 1e6,
               args={"wait": "build"} if name == "jax.backend_compile"
               else None)


def _refresh_enabled() -> None:
    global ENABLED, _ANNOTATION
    on = _GLOBAL is not None or bool(_SCOPED)
    if on and _ANNOTATION is None:
        # first attach of the process: bind what needs JAX (never at import)
        from tidb_tpu.ops.jax_env import jax
        _ANNOTATION = jax.profiler.TraceAnnotation
        jax.monitoring.register_event_duration_secs_listener(
            _on_jax_duration)
    ENABLED = on


def now_us() -> float:
    return (time.perf_counter() - _T0) * 1e6


def bind(pid: int = 0, req: int = 0) -> None:
    """This thread now runs statement work of connection `pid`, request
    `req` (util/phases.set_current calls it; zeros unbind): what a span
    with no enclosing span and no ids of its own records under — a
    collector attached in mid-request (TRACE FORMAT='chrome') never saw
    the request's root open."""
    _tls.bound = (pid, req) if pid or req else None


def new_request_id() -> int:
    """Mint the id every span of one request carries (server command loop,
    or Session.execute when no server is above it)."""
    return next(_REQUEST_IDS)


def _append(ev: dict) -> None:
    with _LOCK:
        if _GLOBAL is not None:
            _GLOBAL.events.append(ev)
            _GLOBAL.dirty = True
        for c in _SCOPED:
            c.events.append(ev)


# ---- spans ----------------------------------------------------------------

class _NoSpan:
    """What `span()` hands out while nothing is attached."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NO_SPAN = _NoSpan()


class _Span:
    __slots__ = ("name", "lane", "pid", "req", "args", "id", "parent",
                 "ts", "cpu", "_ann")

    def __init__(self, name, lane, pid, req, args):
        self.name = name
        self.lane = lane
        self.pid = pid
        self.req = req
        self.args = args

    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        up = stack[-1] if stack else None
        if up is not None:
            # the enclosing span on this thread is the parent, and gives
            # the request and connection where the site has neither
            self.parent = up.id
            if self.req is None:
                self.req = up.req
            if self.pid is None:
                self.pid = up.pid
        else:
            self.parent = 0
            bound = getattr(_tls, "bound", None)
            if bound is not None:
                if self.pid is None:
                    self.pid = bound[0]
                if self.req is None:
                    self.req = bound[1]
        self.id = next(_SPAN_IDS)
        stack.append(self)
        req, pid = self.req or 0, self.pid or 0
        self.ts = now_us()
        self.cpu = time.thread_time_ns()    # both clocks at one place
        # a root also carries the timeline's clock into the profile
        clock = {"ts_us": self.ts} if up is None else {}
        self._ann = _ANNOTATION(f"tidb_tpu/{self.lane}/{self.name}",
                                req=req, conn=pid, **clock)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        end = now_us()
        cpu = time.thread_time_ns() - self.cpu
        stack = _tls.stack
        if self in stack:
            del stack[stack.index(self):]
        args = self.args
        args["req"] = self.req or 0
        args["id"] = self.id
        args["parent"] = self.parent
        args["cpu"] = round(cpu * 1e-3, 1)  # µs this thread ran inside it
        _append({"name": self.name, "cat": self.lane, "ph": "X",
                 "ts": round(self.ts, 1), "dur": round(end - self.ts, 1),
                 "pid": int(self.pid or 0),
                 "tid": STREAMS.get(self.lane, _OTHER_TID), "args": args})
        return False


def span(name: str, lane: str, pid: Optional[int] = None,
         req: Optional[int] = None, **args):
    """Context manager around one piece of host work: THE recording site.
    Off → one shared no-op object. On → an "X" event on `lane` with the
    request id, its own id and its parent's (the enclosing span of this
    thread, which also supplies `pid`/`req` where the site has neither),
    `cpu` (µs this thread ran inside it), and a
    `jax.profiler.TraceAnnotation` of the same extent. A site that blocks
    by design passes `wait=<kind>` (module docstring)."""
    if not ENABLED:
        return _NO_SPAN
    return _Span(name, lane, pid, req, args)


def tag(**args) -> None:
    """Add tags to the innermost open span of this thread (a cache lookup
    that turned out a hit, inside the span that covers the lookup)."""
    if not ENABLED:
        return
    stack = getattr(_tls, "stack", None)
    if stack:
        stack[-1].args.update(args)


class _NamedLock:
    """What `named_lock` hands out: a `threading.Lock` / `RLock` whose
    CONTENDED acquires are `lock.wait` spans while the recorder is on."""
    __slots__ = ("name", "_lock", "release")

    def __init__(self, name: str, lock):
        self.name = name
        self._lock = lock
        self.release = lock.release

    def acquire(self, blocking: bool = True, timeout: float = -1,
                span: str = "lock.wait") -> bool:
        """`span`: a site whose wait for this lock is a step of its own
        design records it under its own name (`commit.gate`), in the same
        lane."""
        if not ENABLED:
            return self._lock.acquire(blocking, timeout)
        if self._lock.acquire(False):       # free, or this thread's RLock
            return True
        if not blocking:
            return False
        with _Span(span, "lock", None, None,
                   {"name": self.name, "wait": "lock"}):
            return self._lock.acquire(True, timeout)

    __enter__ = acquire

    def __exit__(self, *exc):
        self._lock.release()


def named_lock(name: str, reentrant: bool = False) -> _NamedLock:
    """One of the program's own locks on the statement path, under a name.
    Off, or on and uncontended: the plain acquire, no span, no clock read.
    On and contended: a `lock.wait` span on lane `lock` (`name=<name>`,
    `wait=lock`) around the blocking acquire. Behaves as the
    `threading.Lock` (`reentrant`: `RLock`) it wraps."""
    return _NamedLock(name,
                      threading.RLock() if reentrant else threading.Lock())


def record(name: str, stream: str, dur_us: float = 0.0, pid: int = 0,
           ts_us: Optional[float] = None, args: Optional[dict] = None,
           ph: str = "X") -> None:
    """Append one complete ("X") or instant ("i") event whose duration
    was measured elsewhere (a queue wait the scheduler timed, a compile).
    `ts_us` is the START timestamp; when omitted the event is assumed to
    END now (ts = now - dur). The enclosing span of this thread, if any,
    gives the request id and the parent."""
    if not ENABLED:
        return
    end = now_us()
    ts = ts_us if ts_us is not None else max(end - dur_us, 0.0)
    ev = {"name": name, "cat": stream, "ph": ph,
          "ts": round(ts, 1), "pid": int(pid),
          "tid": STREAMS.get(stream, _OTHER_TID)}
    if ph == "X":
        ev["dur"] = round(max(dur_us, 0.0), 1)
    else:
        ev["s"] = "g"
    stack = getattr(_tls, "stack", None)
    if stack:
        up = stack[-1]
        args = dict(args or (), req=up.req or 0, parent=up.id)
        if not pid:
            ev["pid"] = int(up.pid or 0)
    else:
        bound = getattr(_tls, "bound", None)
        if bound is not None:
            args = dict(args or (), req=bound[1])
            if not pid:
                ev["pid"] = bound[0]
    if args:
        ev["args"] = args
    _append(ev)


def instant(name: str, stream: str, pid: int = 0,
            args: Optional[dict] = None) -> None:
    if ENABLED:
        record(name, stream, pid=pid, ts_us=now_us(), args=args, ph="i")


def _gc_event(phase: str, info: dict) -> None:
    """gc.callbacks hook while the global collector is attached: one `gc`
    span per generation-2 collection, on the thread that triggered it."""
    if info.get("generation") != 2:
        return
    if phase == "start":
        _tls.gc_t0 = now_us()
    else:
        t0 = getattr(_tls, "gc_t0", None)
        if t0 is not None:
            _tls.gc_t0 = None
            record("gc.gen2", "gc", dur_us=now_us() - t0, ts_us=t0,
                   args={"collected": info.get("collected", 0)})


# ---- global (tidb_tpu_trace_dir) collector --------------------------------

def start_global(trace_dir: str) -> str:
    """Idempotently attach the process-global collector writing to
    <trace_dir>/tidb_tpu_trace_<pid>.json.  → the file path."""
    global _GLOBAL, _GLOBAL_PATH, _NEXT_FLUSH
    with _LOCK:
        if _GLOBAL is None:
            _GLOBAL = _Collector()
            _LAST.clear()
            _NEXT_FLUSH = time.monotonic() + FLUSH_INTERVAL_S
            if _gc_event not in gc.callbacks:
                gc.callbacks.append(_gc_event)
        _GLOBAL_PATH = os.path.join(
            str(trace_dir), f"tidb_tpu_trace_{os.getpid()}.json")
    _refresh_enabled()
    return _GLOBAL_PATH


def stop_global() -> None:
    global _GLOBAL, _GLOBAL_PATH
    flush()
    with _LOCK:
        if _GLOBAL is not None:
            _LAST[:] = _GLOBAL.events
        _GLOBAL = None
        _GLOBAL_PATH = None
        if _gc_event in gc.callbacks:
            gc.callbacks.remove(_gc_event)
    _refresh_enabled()


def global_path() -> Optional[str]:
    return _GLOBAL_PATH


def last_events() -> List[dict]:
    """The global collector's events: the running one's so far, else the
    last stopped one's (kept until the next `start_global`)."""
    with _LOCK:
        return list(_GLOBAL.events if _GLOBAL is not None else _LAST)


def flush_if_due() -> None:
    """The statement path's call, once a statement: render what has been
    recorded once RENDER_BATCH events are pending (so a statement pays for
    about its own events, ≈ 1 ms a batch, and never for a window's), and
    write the file at most once every FLUSH_INTERVAL_S. Otherwise it costs
    one clock read."""
    global _NEXT_FLUSH
    c = _GLOBAL
    if c is None:
        return
    t0 = time.monotonic()
    if t0 >= _NEXT_FLUSH:
        _NEXT_FLUSH = t0 + FLUSH_INTERVAL_S     # no second thread starts one
        flush()
    elif (len(c.events) - c.rendered >= RENDER_BATCH
          and _FLUSH_LOCK.acquire(False)):      # busy: the holder renders
        try:
            _render_pending(c)
        finally:
            _FLUSH_LOCK.release()


def _render_pending(c: _Collector) -> None:
    """Render what `c` recorded since its last rendering into one more
    chunk of the file's text, in `ts` order within the chunk, and note the
    events' lanes. The caller holds `_FLUSH_LOCK`."""
    with _LOCK:
        events = c.events[c.rendered:]
        c.rendered = len(c.events)
    if not events:
        return
    events.sort(key=operator.itemgetter("ts"))
    for pid, tid, cat in {(e["pid"], e["tid"], e["cat"]) for e in events}:
        c.lanes.setdefault((pid, tid), cat)
    c.chunks.append(json.dumps(events)[1:-1].encode())


def _pieces(chunks: List[bytes]) -> List[bytes]:
    """The rendered chunks joined into pieces of at least WRITE_PIECE
    bytes (the last may be smaller): every `write` gives the interpreter
    away and queues to get it back, so the file goes out in few large
    ones; a full piece is never copied again."""
    out: List[bytes] = []
    run: List[bytes] = []
    size = 0
    for chunk in chunks:
        if not run and len(chunk) >= WRITE_PIECE:
            out.append(chunk)
            continue
        run.append(chunk)
        size += len(chunk)
        if size >= WRITE_PIECE:
            out.append(b", ".join(run))
            run, size = [], 0
    if run:
        out.append(b", ".join(run))
    return out


def flush() -> Optional[str]:
    """Write the global collector's events to its JSON file (atomic
    tmp+rename).  → the path, or None when nothing is attached or the
    write failed. An event is rendered ONCE (under the interpreter's
    lock: the statement path does it a batch at a time, `flush_if_due`,
    and this renders what is left); the text of the earlier ones is kept
    and written again as bytes, in pieces of WRITE_PIECE, and a file
    write releases the lock."""
    with _FLUSH_LOCK:
        with _LOCK:
            c, path = _GLOBAL, _GLOBAL_PATH
            if c is None or path is None or not c.dirty:
                return path
            c.dirty = False
        _render_pending(c)
        head = json.dumps(_metadata(c.lanes))[:-1].encode()
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = path + ".tmp"
            c.chunks = _pieces(c.chunks)
            with open(tmp, "wb") as f:
                f.write(b'{"traceEvents": ' + head)
                for piece in c.chunks:
                    f.write(b", " + piece)
                f.write(b'], "displayTimeUnit": "ms"}')
            os.replace(tmp, path)
        except OSError:
            # tracing must never sink the statement that triggered the
            # flush; what was rendered leaves with the next write
            c.dirty = True
            return None
        return path


# ---- scoped capture (TRACE FORMAT='chrome') -------------------------------

@contextmanager
def capture():
    """Collect every event recorded while the context is active —
    the statement-scoped sink behind TRACE FORMAT='chrome'."""
    c = _Collector()
    with _LOCK:
        _SCOPED.append(c)
    _refresh_enabled()
    try:
        yield c
    finally:
        with _LOCK:
            try:
                _SCOPED.remove(c)
            except ValueError:
                pass
        _refresh_enabled()


def _metadata(lanes: Dict[tuple, str]) -> List[dict]:
    """process_name / thread_name events for {(pid, tid): lane}."""
    meta: List[dict] = []
    for pid in sorted({p for p, _ in lanes}):
        meta.append({"name": "process_name", "ph": "M", "pid": pid,
                     "tid": 0, "args": {"name": f"conn {pid}"}})
    for (pid, tid), cat in sorted(lanes.items()):
        meta.append({"name": "thread_name", "ph": "M", "pid": pid,
                     "tid": tid, "args": {"name": cat}})
    return meta


def render(events: List[dict]) -> str:
    """Chrome-trace JSON: events sorted by ts (so every tid's sequence is
    monotonically non-decreasing) plus process/thread_name metadata."""
    ordered = sorted(events, key=lambda e: e["ts"])
    seen: Dict[tuple, str] = {}
    for e in ordered:
        seen.setdefault((e["pid"], e["tid"]), e["cat"])
    return json.dumps({"traceEvents": _metadata(seen) + ordered,
                       "displayTimeUnit": "ms"})


__all__ = ["ENABLED", "STREAMS", "FLUSH_INTERVAL_S", "span", "tag", "bind",
           "record", "instant", "named_lock", "new_request_id", "start_global",
           "stop_global", "global_path", "last_events", "flush",
           "flush_if_due", "capture", "render", "now_us"]
