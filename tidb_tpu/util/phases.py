"""Per-query device phase timing — the overlap runtime's observability.

The streamed first-touch pipeline (executor/device_cache.open_table +
fragment._execute_*, agg_slabs.run_agg_slabs) interleaves host encode of slab k+1 with the async
upload/compute of slab k. This module measures where the wall time went
and how much host work was actually hidden behind device activity:

  encode   host-side column materialize + dictionary build + per-slab
           code/pad work (numpy, blocking);
  upload   time spent issuing jax.device_put / jnp.asarray transfers
           (async dispatch — the transfer itself overlaps);
  compute  time spent issuing jitted partial/merge calls plus the final
           drain wait (block_until_ready) for the device to finish — on
           the timeline the two are told apart: one `launch` span per
           jitted call (`PhaseTimer.launch`), one `drain` span per wait
           (`PhaseTimer.drain`), both in this one seconds ledger;
  fetch    device→host result transfers (jax.device_get round trips);
  decode   host-side dictionary decode / Chunk assembly.

Overlap efficiency is defined measurably, not aspirationally: the
fraction of host `encode` seconds that elapsed while device work was
already in flight (at least one slab uploaded/dispatched). A cold
single-slab table can overlap nothing (0.0); an n-slab streamed cold
start approaches (n-1)/n; the serial encode-all/upload-all/run shape
scores 0.0 by construction.

Beyond seconds, the PhaseTimer is the statement's attribution ledger
(the stmtsummary/execdetails analog): host→device bytes uploaded
(h2d_bytes), device→host bytes fetched (d2h_bytes), HBM bytes the
device program read (scan_bytes — resident column slabs touched, warm
or cold), and XLA trace/compile count (compiles). ExecutionGuard owns
one per statement; every ExecContext of that statement shares it, so
EXPLAIN ANALYZE, the statements_summary digest profile, the slow log
and the Chrome timeline all read the SAME counters.

A thread-local `current()` pointer (set by Session.execute around each
statement) lets sites with no ExecContext in reach — the single-flight
program builders, cache evictions — attribute to the running statement.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Dict, Optional

from tidb_tpu.util import timeline

PHASES = ("encode", "upload", "compute", "fetch", "decode")

_tls = threading.local()


def set_current(pt: Optional["PhaseTimer"]) -> None:
    """Bind `pt` as this thread's running statement's PhaseTimer (None to
    clear).  Statement execution is single-threaded per connection, so
    compile/eviction sites reached from the statement's call stack can
    attribute to it without threading a context through every layer."""
    _tls.pt = pt
    if pt is None:
        timeline.bind()
    else:
        timeline.bind(pt.conn_id, pt.req)


def current() -> Optional["PhaseTimer"]:
    return getattr(_tls, "pt", None)


class PhaseTimer:
    """Per-statement phase accumulator (ExecContext.phases)."""

    __slots__ = ("seconds", "overlapped_s", "wall_s", "_in_flight",
                 "h2d_bytes", "d2h_bytes", "scan_bytes", "compiles",
                 "programs_launched", "fused_pipelines",
                 "specialization_hits", "conn_id",
                 "h2d_logical_bytes", "scan_logical_bytes",
                 "slabs_skipped", "h2d_skipped_bytes", "delta_rows",
                 "_delta_seen", "device_index", "tables", "req")

    def __init__(self, conn_id: int = 0, req: int = 0):
        self.seconds: Dict[str, float] = {p: 0.0 for p in PHASES}
        self.overlapped_s = 0.0   # encode seconds with device work in flight
        self.wall_s = 0.0         # device-path wall (set by the executor)
        self._in_flight = False
        self.h2d_bytes = 0        # host→device upload bytes (physical)
        self.d2h_bytes = 0        # device→host fetch bytes
        self.scan_bytes = 0       # HBM column bytes the program read
        # logical twins: bytes the same transfers/reads WOULD have been
        # with raw (uncompressed) column layouts — physical == logical
        # when compression is off, so the pair quantifies bytes saved
        self.h2d_logical_bytes = 0
        self.scan_logical_bytes = 0
        self.compiles = 0         # XLA program traces charged to this stmt
        self.programs_launched = 0  # jitted device program dispatches
        self.fused_pipelines = 0    # of those, whole-pipeline slab launches
        self.specialization_hits = 0  # per-digest plan-cache hits
        # zone-map pruning ledger: dispatch units (slabs / staged-dist
        # ranks) skipped entirely, and upload bytes a pruned cold slab
        # never moved across PCIe
        self.slabs_skipped = 0
        self.h2d_skipped_bytes = 0
        # delta-slab rows this statement's scans merged in-trace on top
        # of the immutable base (executor/delta.py extensions); charged
        # once per generation read — a statement may open the same
        # cached entry several times (plan build, fragment execute)
        self.delta_rows = 0
        self._delta_seen = set()
        self.conn_id = conn_id    # timeline pid (0 = unattributed)
        # the request this statement belongs to (timeline `req`): sites
        # that reach the ledger through phases.current(), or from another
        # thread, record under it
        self.req = req
        # pod-scale attribution: the device index the statement is
        # pinned to (scheduler placement stamps it; compile caches,
        # metric labels and timeline lanes read it) and the table ids
        # its scans opened — record_stmt folds the set into the digest
        # profile, closing the loop locality placement routes by
        self.device_index = 0
        self.tables = set()

    @contextmanager
    def phase(self, name: str, sig: Optional[str] = None,
              lane: Optional[str] = None, span: Optional[str] = None,
              **tags):
        """The seconds ledger is keyed by `name` alone. The timeline span
        is called `span` on `lane` (both default to `name`); `sig` (the
        fused pipeline's signature digest on per-slab launches) and `tags`
        label it. A `fetch` is a designed wait for the device
        (`wait=device`: every site is a `device_get`, which blocks until
        the result is computed and has crossed)."""
        if name == "fetch":
            tags["wait"] = "device"
        if sig:
            tags["sig"] = sig
        if self.device_index:
            tags["dev"] = self.device_index
        t0 = time.perf_counter()
        try:
            with timeline.span(span or name, lane or name,
                               pid=self.conn_id, req=self.req, **tags):
                yield
        finally:
            dt = time.perf_counter() - t0
            self.seconds[name] = self.seconds.get(name, 0.0) + dt
            if name == "encode" and self._in_flight:
                self.overlapped_s += dt

    def launch(self, program: str, slab: Optional[int] = None,
               sig: Optional[str] = None):
        """`compute` seconds spent issuing ONE jitted call: a `launch` span
        named after the program (`<kind>_<sig8>`). The call returns when
        the program is queued, not when it has run."""
        return self.phase("compute", sig=sig, lane="launch", span=program,
                          program=program, slab=slab)

    def drain(self):
        """`compute` seconds spent waiting for the device to finish what
        was launched (`block_until_ready`): a `drain` span, a designed
        wait for the device (`wait=device`)."""
        return self.phase("compute", lane="drain", span="drain",
                          wait="device")

    def glue(self):
        """`compute` seconds spent issuing the eager device work between
        launches (stacking slab partials, slicing what the next fetch
        takes): a `frag.glue` span, fragment set-up on the timeline."""
        return self.phase("compute", lane="frag", span="frag.glue")

    def mark_in_flight(self) -> None:
        """First slab's device work has been issued: later encode time is
        pipelined behind it."""
        self._in_flight = True

    def clear_in_flight(self) -> None:
        self._in_flight = False

    def add_wall(self, dt: float) -> None:
        self.wall_s += dt

    # -- byte / compile attribution -----------------------------------------
    def add_h2d(self, n: int, logical: int = None) -> None:
        """`logical` is the raw-layout equivalent of the `n` physical
        bytes (defaults to n — uncompressed transfers are 1:1)."""
        self.h2d_bytes += int(n)
        self.h2d_logical_bytes += int(n if logical is None else logical)

    def add_d2h(self, n: int) -> None:
        self.d2h_bytes += int(n)

    def add_scan(self, n: int, logical: int = None) -> None:
        self.scan_bytes += int(n)
        self.scan_logical_bytes += int(n if logical is None else logical)

    def note_compile(self) -> None:
        self.compiles += 1

    def note_launch(self, n: int = 1) -> None:
        """A jitted device program was dispatched (warm or cold)."""
        self.programs_launched += int(n)

    def note_fused(self, n: int = 1) -> None:
        """A dispatched program was a whole-pipeline fused slab launch
        (scan→filter→join-probe→partial-agg in one traced XLA program)."""
        self.fused_pipelines += int(n)

    def note_spec_hit(self, n: int = 1) -> None:
        """The per-digest specialization cache served this statement's
        caps + compile-cache signature (no signature construction, no
        capacity-discovery ladder climb)."""
        self.specialization_hits += int(n)

    def note_slabs_skipped(self, n: int = 1) -> None:
        """Zone maps proved `n` dispatch units (slabs or staged-dist
        rank slices) empty under the scan's conjuncts — no upload, no
        launch, no escalation bookkeeping for them."""
        self.slabs_skipped += int(n)

    def note_h2d_skipped(self, n: int) -> None:
        """A pruned cold slab skipped its encode+upload: `n` physical
        bytes never crossed PCIe (the ledger the bench's zero-H2D
        assertion reads)."""
        self.h2d_skipped_bytes += int(n)

    def note_delta_rows(self, n: int, token: int = None) -> None:
        """This statement read a delta generation carrying `n` appended
        live rows merged in-trace with the base slabs. `token` (the
        generation's identity) dedupes repeat opens of the same entry
        within one statement."""
        if token is not None:
            if token in self._delta_seen:
                return
            self._delta_seen.add(token)
        self.delta_rows += int(n)

    def fetch(self, tree):
        """jax.device_get under the fetch phase, with the transferred
        bytes charged to d2h_bytes — the one chokepoint every result
        round trip should go through."""
        from tidb_tpu.ops.jax_env import jax
        with self.phase("fetch"):
            host = jax.device_get(tree)
        self.add_d2h(tree_nbytes(host))
        return host

    @property
    def total(self) -> float:
        return sum(self.seconds.values())

    def overlap_efficiency(self) -> float:
        enc = self.seconds.get("encode", 0.0)
        if enc <= 0.0:
            return 0.0
        return min(1.0, self.overlapped_s / enc)

    def as_dict(self) -> Dict[str, float]:
        out = {f"{p}_s": round(self.seconds.get(p, 0.0), 4) for p in PHASES}
        out["overlap_efficiency"] = round(self.overlap_efficiency(), 3)
        out["wall_s"] = round(self.wall_s, 4)
        out["h2d_bytes"] = self.h2d_bytes
        out["d2h_bytes"] = self.d2h_bytes
        out["scan_bytes"] = self.scan_bytes
        out["h2d_logical_bytes"] = self.h2d_logical_bytes
        out["scan_logical_bytes"] = self.scan_logical_bytes
        out["compiles"] = self.compiles
        out["programs_launched"] = self.programs_launched
        out["fused_pipelines"] = self.fused_pipelines
        out["specialization_hits"] = self.specialization_hits
        out["slabs_skipped"] = self.slabs_skipped
        out["h2d_skipped_bytes"] = self.h2d_skipped_bytes
        out["delta_rows"] = self.delta_rows
        return out

    def summary(self) -> str:
        """Compact 'enc=0.012s up=0.003s ... ov=0.67' line for EXPLAIN
        ANALYZE runtime info and the trace."""
        if self.total <= 0.0:
            return ""
        short = {"encode": "enc", "upload": "up", "compute": "comp",
                 "fetch": "fetch", "decode": "dec"}
        parts = [f"{short[p]}={self.seconds[p]:.3f}s" for p in PHASES
                 if self.seconds.get(p, 0.0) > 0.0005]
        parts.append(f"ov={self.overlap_efficiency():.2f}")
        if self.h2d_bytes or self.d2h_bytes:
            parts.append(f"h2d={self.h2d_bytes}B d2h={self.d2h_bytes}B")
        if self.h2d_logical_bytes != self.h2d_bytes or \
                self.scan_logical_bytes != self.scan_bytes:
            # compression active: show the raw-equivalent byte counts
            parts.append(f"h2d_logical={self.h2d_logical_bytes}B "
                         f"scan_logical={self.scan_logical_bytes}B")
        if self.compiles:
            parts.append(f"compiles={self.compiles}")
        if self.programs_launched:
            parts.append(f"launches={self.programs_launched} "
                         f"fused={self.fused_pipelines}")
        if self.specialization_hits:
            parts.append(f"spec_hits={self.specialization_hits}")
        if self.slabs_skipped:
            parts.append(f"skipped={self.slabs_skipped} "
                         f"h2d_skipped={self.h2d_skipped_bytes}B")
        return " ".join(parts)


def tree_nbytes(tree) -> int:
    """Total nbytes of every array leaf in a (nested) container of host
    arrays — the byte meter behind PhaseTimer.fetch / upload sites."""
    total = 0
    stack = [tree]
    while stack:
        x = stack.pop()
        if x is None:
            continue
        nb = getattr(x, "nbytes", None)
        if nb is not None:
            total += int(nb)
        elif isinstance(x, dict):
            stack.extend(x.values())
        elif isinstance(x, (list, tuple)):
            stack.extend(x)
    return total


__all__ = ["PhaseTimer", "PHASES", "set_current", "current",
           "tree_nbytes"]
