"""Metrics, slow-query log, statement summary (ref: metrics/metrics.go:68,
executor/slow_query.go:59, util/stmtsummary/statement_summary.go:66).

The reference registers ~17 Prometheus collectors and exposes them over
HTTP; queries can also read the slow log and statement summaries as SQL
tables. Here one process-wide registry backs all three surfaces:

  * counters + histograms, rendered in Prometheus text format
    (`render_prometheus`) and served by util/status_server.py;
  * a slow-query ring buffer (threshold: `long_query_time` sysvar);
  * per-SQL-digest statement summaries — TopSQL-style device-time
    attribution: wall seconds, device seconds, host↔device bytes,
    compile counts and a queue-wait histogram (p50/p99) per digest,
    fed by each statement's PhaseTimer/ExecutionGuard via record_stmt.

SQL surfaces: SHOW METRICS / SHOW SLOW QUERIES / SHOW STATEMENT SUMMARY
/ SHOW PROCESSLIST (session/__init__.py wires them), plus the
information_schema.statements_summary / slow_query / engine_metrics
memtables (infoschema_tables.py)."""

from __future__ import annotations

import functools
import re
import time
from collections import OrderedDict, deque
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

from tidb_tpu.util import timeline

_BUCKETS = (0.001, 0.005, 0.02, 0.1, 0.5, 2.0, 10.0)
# executions a digest's cost hint looks back over (an exponential mean of
# that weight): a first touch is forgotten within a few dozen
COST_RUNS = 8


def _hist_new() -> list:
    return [[0] * (len(_BUCKETS) + 1), 0.0, 0]      # buckets, sum, n


def _hist_observe(h: list, value: float) -> None:
    i = 0
    while i < len(_BUCKETS) and value > _BUCKETS[i]:
        i += 1
    h[0][i] += 1
    h[1] += value
    h[2] += 1


def hist_quantile(h: list, q: float) -> float:
    """Approximate quantile from cumulative bucket counts (the Prometheus
    histogram_quantile estimate): linear interpolation inside the target
    bucket, with the overflow bucket clamped to the last bound."""
    bk, s, n = h
    if n <= 0 or s <= 0.0:
        # no observations — or all exactly zero (e.g. statements that
        # never queued): the quantile is 0, not an interpolated slice of
        # the first bucket
        return 0.0
    target = q * n
    acc = 0
    lo = 0.0
    for i, cnt in enumerate(bk):
        if cnt == 0:
            continue
        hi = _BUCKETS[i] if i < len(_BUCKETS) else _BUCKETS[-1]
        if acc + cnt >= target:
            frac = (target - acc) / cnt
            return lo + (hi - lo) * min(max(frac, 0.0), 1.0)
        acc += cnt
        lo = hi
    return _BUCKETS[-1]


class Registry:
    def __init__(self):
        self._lock = timeline.named_lock("metrics")
        self.counters: Dict[Tuple[str, Tuple], float] = {}
        self.hists: Dict[Tuple[str, Tuple], List] = {}
        self.slow_log: deque = deque(maxlen=256)
        self.stmt_summary: "OrderedDict[str, dict]" = OrderedDict()

    # -- metrics -----------------------------------------------------------
    def inc(self, name: str, labels: Dict[str, str] = None, by: float = 1):
        key = (name, tuple(sorted((labels or {}).items())))
        with self._lock:
            self.counters[key] = self.counters.get(key, 0) + by

    def set_gauge(self, name: str, value: float,
                  labels: Dict[str, str] = None):
        """Set-point metric (e.g. per-device health flags): stored and
        rendered alongside the counters, last write wins."""
        key = (name, tuple(sorted((labels or {}).items())))
        with self._lock:
            self.counters[key] = float(value)

    def observe(self, name: str, value: float,
                labels: Dict[str, str] = None):
        key = (name, tuple(sorted((labels or {}).items())))
        with self._lock:
            h = self.hists.get(key)
            if h is None:
                h = self.hists[key] = _hist_new()
            _hist_observe(h, value)

    def metric_rows(self) -> List[tuple]:
        """SHOW METRICS rows. Histograms emit per-bucket CUMULATIVE rows
        (`name_bucket` with an `le=` label, matching render_prometheus)
        ahead of `_count`/`_sum` — without the buckets no percentile can
        be derived from SQL."""
        with self._lock:
            out = []
            for (name, labels), v in sorted(self.counters.items()):
                lbl = ",".join(f"{k}={val}" for k, val in labels)
                out.append((name, lbl, float(v)))
            for (name, labels), (bk, s, n) in sorted(self.hists.items()):
                lbl = ",".join(f"{k}={val}" for k, val in labels)
                sep = "," if lbl else ""
                acc = 0
                for b, cnt in zip(_BUCKETS, bk):
                    acc += cnt
                    out.append((name + "_bucket", f"{lbl}{sep}le={b}",
                                float(acc)))
                out.append((name + "_bucket", f"{lbl}{sep}le=+Inf",
                            float(n)))
                out.append((name + "_count", lbl, float(n)))
                out.append((name + "_sum", lbl, round(s, 6)))
            return out

    def render_prometheus(self) -> str:
        lines = []
        with self._lock:
            for (name, labels), v in sorted(self.counters.items()):
                lines.append(f"{name}{_fmt_labels(labels)} {v}")
            for (name, labels), (bk, s, n) in sorted(self.hists.items()):
                acc = 0
                for b, cnt in zip(_BUCKETS, bk):
                    acc += cnt
                    lines.append(
                        f'{name}_bucket{_fmt_labels(labels, ("le", b))} '
                        f"{acc}")
                lines.append(
                    f'{name}_bucket{_fmt_labels(labels, ("le", "+Inf"))} '
                    f"{n}")
                lines.append(f"{name}_sum{_fmt_labels(labels)} {s}")
                lines.append(f"{name}_count{_fmt_labels(labels)} {n}")
        return "\n".join(lines) + "\n"

    # -- statement-level records -------------------------------------------
    def record_stmt(self, sql: str, seconds: float, rows: int,
                    engine: str, threshold: float, guard=None):
        """Fold one finished statement into its digest profile.  `guard`
        (the statement's ExecutionGuard) carries the attribution ledger:
        guard.phases (PhaseTimer — device wall, per-phase seconds,
        h2d/d2h/scan bytes, compile count) and guard.queue_wait_s /
        queue_waits (device-scheduler admission).  All counters aggregate
        ADDITIVELY per digest, so a profile row equals the exact sum of
        its statements' EXPLAIN ANALYZE phase totals."""
        digest = normalize_sql(sql)
        now = time.time()
        ph = getattr(guard, "phases", None) if guard is not None else None
        queue_wait_s = float(getattr(guard, "queue_wait_s", 0.0) or 0.0) \
            if guard is not None else 0.0
        with self._lock:
            s = self.stmt_summary.get(digest)
            if s is None:
                s = {"digest": digest, "count": 0, "sum_s": 0.0,
                     "max_s": 0.0, "rows": 0, "last_seen": 0.0,
                     "device_s": 0.0, "h2d_bytes": 0, "d2h_bytes": 0,
                     "scan_bytes": 0, "h2d_logical_bytes": 0,
                     "scan_logical_bytes": 0, "compiles": 0,
                     "programs_launched": 0, "fused_pipelines": 0,
                     "specialization_hits": 0,
                     "slabs_skipped": 0, "h2d_skipped_bytes": 0,
                     "delta_rows": 0,
                     "queue_wait_s": 0.0, "queue_waits": 0,
                     "queue_hist": _hist_new(),
                     "sched_class": None, "cost_s": None,
                     "phase_s": {}, "engine": engine}
                self.stmt_summary[digest] = s
                while len(self.stmt_summary) > 512:
                    self.stmt_summary.popitem(last=False)
            s["count"] += 1
            s["sum_s"] += seconds
            s["max_s"] = max(s["max_s"], seconds)
            s["rows"] += rows
            s["last_seen"] = now
            s["engine"] = engine
            s["queue_wait_s"] += queue_wait_s
            s["queue_waits"] += int(getattr(guard, "queue_waits", 0) or 0) \
                if guard is not None else 0
            _hist_observe(s["queue_hist"], queue_wait_s)
            cls = getattr(guard, "sched_class", None) \
                if guard is not None else None
            if cls is not None:
                # last-writer wins: the digest's class is stable by
                # construction (same digest → same classification)
                s["sched_class"] = cls
                dev = getattr(guard, "device_index", None)
                key = ("tidb_tpu_queue_wait_seconds",
                       (("class", cls),
                        ("device", str(dev if dev is not None else 0))))
                h = self.hists.get(key)
                if h is None:
                    h = self.hists[key] = _hist_new()
                _hist_observe(h, queue_wait_s)
            if ph is not None:
                s["device_s"] += ph.wall_s
                # the scheduler's cost hint (`digest_cost`): what the
                # device path takes once the statement HAS the slot, as
                # of late
                own = max(ph.wall_s - queue_wait_s, 0.0)
                s["cost_s"] = own if s["cost_s"] is None else \
                    s["cost_s"] + (own - s["cost_s"]) / COST_RUNS
                s["h2d_bytes"] += ph.h2d_bytes
                s["d2h_bytes"] += ph.d2h_bytes
                s["scan_bytes"] += ph.scan_bytes
                s["h2d_logical_bytes"] += getattr(
                    ph, "h2d_logical_bytes", ph.h2d_bytes)
                s["scan_logical_bytes"] += getattr(
                    ph, "scan_logical_bytes", ph.scan_bytes)
                s["compiles"] += ph.compiles
                s["programs_launched"] += ph.programs_launched
                s["fused_pipelines"] += ph.fused_pipelines
                s["specialization_hits"] += getattr(
                    ph, "specialization_hits", 0)
                s["slabs_skipped"] += getattr(ph, "slabs_skipped", 0)
                s["h2d_skipped_bytes"] += getattr(
                    ph, "h2d_skipped_bytes", 0)
                s["delta_rows"] += getattr(ph, "delta_rows", 0)
                tabs = getattr(ph, "tables", None)
                if tabs:
                    # the statement's table footprint (open_table records
                    # every device-path scan) — locality placement reads
                    # it back per digest via digest_tables
                    s.setdefault("tables", set()).update(tabs)
                for p, v in ph.seconds.items():
                    s["phase_s"][p] = s["phase_s"].get(p, 0.0) + v
            if seconds >= threshold:
                entry = {
                    "time": now, "query": sql[:2048],
                    "duration_s": round(seconds, 6), "rows": rows,
                    "engine": engine,
                    "queue_wait_ms": round(queue_wait_s * 1000.0, 3)}
                if ph is not None:
                    entry["device_s"] = round(ph.wall_s, 6)
                    entry["h2d_bytes"] = ph.h2d_bytes
                    entry["compiles"] = ph.compiles
                else:
                    entry["device_s"] = 0.0
                    entry["h2d_bytes"] = 0
                    entry["compiles"] = 0
                self.slow_log.append(entry)

    def digest_cost(self, sql: str) -> Optional[float]:
        """Device seconds of this statement's digest as of its last
        COST_RUNS executions or so, its own waits for the slot left out —
        the scheduler's batch cost hint (None until the digest has run
        with device attribution at least once). Not the lifetime mean: a
        first touch of seconds kept a digest over the cheap/heavy line
        (scheduler.CHEAP_BATCH_S) for hundreds of executions. And not
        the wait: ranked heavy, a statement waits for the slot behind
        every cheap one, and counting that wait as its cost kept it
        heavy — Q1 beside seven other connections at SF=1 stayed so for
        a whole window once the others' programs grew shorter (PERF.md
        §6, PR 41)."""
        digest = normalize_sql(sql)
        with self._lock:
            s = self.stmt_summary.get(digest)
            return None if s is None or not s["cost_s"] else s["cost_s"]

    def digest_tables(self, sql: str) -> Optional[list]:
        """Table ids this statement's digest historically opened on the
        device path — the pool's locality-placement handoff (None until
        the digest has run with table attribution at least once)."""
        digest = normalize_sql(sql)
        with self._lock:
            s = self.stmt_summary.get(digest)
            if s is None:
                return None
            tabs = s.get("tables")
            return sorted(tabs) if tabs else None

    def slow_rows(self) -> List[tuple]:
        with self._lock:
            return [(time.strftime("%Y-%m-%d %H:%M:%S",
                                   time.localtime(e["time"])),
                     e["duration_s"], e["rows"], e["engine"], e["query"])
                    for e in reversed(self.slow_log)]

    def slow_rows_full(self) -> List[tuple]:
        """information_schema.slow_query rows: the ring with the device
        attribution columns."""
        with self._lock:
            return [(time.strftime("%Y-%m-%d %H:%M:%S",
                                   time.localtime(e["time"])),
                     e["duration_s"], e.get("device_s", 0.0),
                     e.get("queue_wait_ms", 0.0),
                     e.get("h2d_bytes", 0), e.get("compiles", 0),
                     e["rows"], e["engine"], e["query"])
                    for e in reversed(self.slow_log)]

    def summary_rows(self) -> List[tuple]:
        with self._lock:
            out = [(s["digest"], s["count"], round(s["sum_s"], 6),
                    round(s["sum_s"] / max(s["count"], 1), 6),
                    round(s["max_s"], 6), s["rows"])
                   for s in self.stmt_summary.values()]
        out.sort(key=lambda r: -r[2])
        return out

    def summary_profiles(self) -> List[dict]:
        """TopSQL-style per-digest profiles, heaviest cumulative wall
        first — the statements_summary / /statements payload."""
        with self._lock:
            out = []
            for s in self.stmt_summary.values():
                qh = s["queue_hist"]
                out.append({
                    "digest": s["digest"], "count": s["count"],
                    "sum_s": round(s["sum_s"], 6),
                    "avg_s": round(s["sum_s"] / max(s["count"], 1), 6),
                    "max_s": round(s["max_s"], 6), "rows": s["rows"],
                    "engine": s["engine"],
                    "device_s": round(s["device_s"], 6),
                    "h2d_bytes": s["h2d_bytes"],
                    "d2h_bytes": s["d2h_bytes"],
                    "scan_bytes": s["scan_bytes"],
                    "h2d_logical_bytes": s.get("h2d_logical_bytes", 0),
                    "scan_logical_bytes": s.get("scan_logical_bytes", 0),
                    "compiles": s["compiles"],
                    "programs_launched": s.get("programs_launched", 0),
                    "fused_pipelines": s.get("fused_pipelines", 0),
                    "specialization_hits": s.get("specialization_hits", 0),
                    "slabs_skipped": s.get("slabs_skipped", 0),
                    "h2d_skipped_bytes": s.get("h2d_skipped_bytes", 0),
                    "delta_rows": s.get("delta_rows", 0),
                    "queue_wait_s": round(s["queue_wait_s"], 6),
                    "queue_waits": s["queue_waits"],
                    "queue_p50_ms": round(
                        hist_quantile(qh, 0.50) * 1000.0, 3),
                    "queue_p99_ms": round(
                        hist_quantile(qh, 0.99) * 1000.0, 3),
                    "sched_class": s.get("sched_class"),
                    "phase_s": {k: round(v, 6)
                                for k, v in s["phase_s"].items()},
                    "last_seen": s["last_seen"],
                })
        out.sort(key=lambda r: -r["sum_s"])
        return out

    # -- processlist --------------------------------------------------------
    # One source of truth: the session-level ProcessRegistry
    # (util/guard.PROCESS_REGISTRY).  The registry used to keep its own
    # conn_id → sql map updated in Session.execute, which could disagree
    # with the privilege-filtered information_schema.processlist; now it
    # only delegates.
    def process_rows(self) -> List[tuple]:
        from tidb_tpu.util.guard import PROCESS_REGISTRY
        return [(cid, round(guard.elapsed(), 3), guard.sql)
                for cid, _user, guard, _killed
                in sorted(PROCESS_REGISTRY.snapshot())
                if guard is not None]


def _fmt_labels(labels: Tuple, extra: Optional[Tuple] = None) -> str:
    items = list(labels)
    if extra is not None:
        items.append(extra)
    if not items:
        return ""
    return "{" + ",".join(f'{k}="{v}"' for k, v in items) + "}"


_NORM_NUM = re.compile(r"\b\d+(\.\d+)?\b")
_NORM_STR = re.compile(r"'(?:[^'\\]|\\.)*'")
_NORM_WS = re.compile(r"\s+")
# a '-' directly after a comparison/arithmetic operator, an opening
# paren, a comma, or an expression-starting keyword is a SIGN, not a
# binary minus — fold it into the placeholder so `x = -5` and `x = 5`
# share one digest
_NORM_SIGN = re.compile(
    r"((?:[=<>(,+*/%-]|\b(?:select|where|and|or|when|then|else|by|limit|"
    r"offset|having|in|between|like|not|set|values|return|on)\b)\s*)-\s*\?",
    re.IGNORECASE)


def normalize_sql(sql: str) -> str:
    """SQL digest: literals → ?, collapsed whitespace (the reference's
    parser.Normalize). One statement asks for its digest several times
    (the summary, the scheduler's cost and placement hints, a fragment's
    specialization key — five times a TPC-H Q1, 0.07 ms each), so short
    texts are remembered; a bulk INSERT's is not kept."""
    if len(sql) > _NORM_MEMO_TEXT:
        return _normalize(sql)
    return _normalize_memo(sql)


def _normalize(sql: str) -> str:
    s = _NORM_STR.sub("?", sql)
    s = _NORM_NUM.sub("?", s)
    # collapse unary sign into the placeholder (repeat for `- - 5`)
    prev = None
    while prev != s:
        prev = s
        s = _NORM_SIGN.sub(r"\1?", s)
    s = _NORM_WS.sub(" ", s).strip()
    # collapse IN/VALUES lists so bulk inserts share one digest
    s = re.sub(r"\((\s*\?\s*,)+\s*\?\s*\)", "(?)", s)
    s = re.sub(r"(\(\?\)\s*,\s*)+\(\?\)", "(?)", s)
    return s[:512]


_NORM_MEMO_TEXT = 4096      # characters: the longest text worth keeping
_normalize_memo = functools.lru_cache(maxsize=1024)(_normalize)


REGISTRY = Registry()

# the stages of a column's first touch, host encode to device upload
FIRST_TOUCH_STAGES = ("materialize", "layout", "dict", "pack", "upload")


@contextmanager
def first_touch(stage: str, **tags):
    """One stage of one column's (or column slab's) first touch: its
    seconds go to the always-on counter
    `tidb_tpu_first_touch_seconds_total{stage=...}` — first touch happens
    once, usually before anyone has switched the timeline on — and, while
    the timeline is on, to an `encode.<stage>` span (`upload` has its own
    lane through PhaseTimer and gets the counter only). One addition per
    column and slab; the warm path never comes here."""
    t0 = time.perf_counter()
    try:
        if stage == "upload":
            yield
        else:
            with timeline.span("encode." + stage, "encode", **tags):
                yield
    finally:
        REGISTRY.inc("tidb_tpu_first_touch_seconds_total",
                     {"stage": stage}, by=time.perf_counter() - t0)
