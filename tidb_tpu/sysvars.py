"""Session variables: every name the engine reads, its default written
ONCE, and the typed readers (ref: sessionctx/variable/tidb_vars.go).

Below every layer that reads one — the executor, the scheduler, the
session — so none of them carries a default of its own: a reader names the
variable, `DEFAULT_VARS` says what it is when nobody set it (a bare
`ExecContext(vars={})` in a test, a tool's context). `SHOW VARIABLES`
prints this table (`Session.vars` starts as a copy of it).
"""

from __future__ import annotations

from typing import Dict, Mapping

DEFAULT_MAX_SLAB_ROWS = 1 << 23   # 8M rows per device slab
# pod partitioning threshold: tables at or above this many rows (by the
# region ledger's approximate count, available before any host collect)
# partition their slab ranges across the pool instead of replicating —
# a per-device replica of a fact table would blow every device's budget
# for no locality win
DEFAULT_PARTITION_MIN_ROWS = 1 << 22

DEFAULT_VARS: Dict[str, object] = {
    # ref: sessionctx/variable/tidb_vars.go — the knobs our engine honors
    "max_chunk_size": 65536,
    "tidb_tpu_engine": "auto",        # on | off | auto (auto: on when TPU)
    "tidb_tpu_row_threshold": 32768,  # min est. rows to route to device
    # staged (checkpointable, per-shard recoverable) distributed agg;
    # off = always the monolithic shard_map program
    "tidb_tpu_dist_staged": "on",
    # staged exchange-carrying fragments (distributed joins, DISTINCT
    # re-keys, windows): partition → device→host bucket checkpoint →
    # per-rank probe, each stage re-dispatchable per rank; off = the
    # monolithic in-trace all_to_all program (the byte-exactness oracle)
    "tidb_tpu_dist_staged_exchange": "on",
    # compressed device-resident columns (bit-pack / frame-of-reference /
    # dictionary) with decode fused into the scan; off = raw layouts
    "tidb_tpu_compression": "on",
    "tidb_mem_quota_query": 8 << 30,
    "sql_mode": "STRICT_TRANS_TABLES",
    "autocommit": 1,
    # statement deadline in ms, 0 = none. Deviation from MySQL (which
    # scopes it to read-only SELECT): applies to EVERY statement — the
    # never-hang guarantee matters more here than MySQL fidelity
    "max_execution_time": 0,
    # when non-empty, every session records its spans, packet-in to last
    # byte out, into ONE Chrome-trace JSON under this directory
    # (util/timeline.py; written every 5 s and on stop) — load it in
    # chrome://tracing or Perfetto
    "tidb_tpu_trace_dir": "",
    # priority-aware serving tier (executor/scheduler.py): classify each
    # admission as interactive/batch and grant the device slot by class;
    # off = the plain FIFO admission order, byte-identical to classless
    "tidb_tpu_priority_scheduling": "on",
    # same-plan micro-batching (executor/microbatch.py): coalesce up to
    # this many queued same-digest statements into ONE batched device
    # program. 1 = parametrize only (shared programs, no coalescing),
    # 0 = literal-baked programs (the pre-serving-tier behavior)
    "tidb_tpu_microbatch_max": 16,
    # one admission queue per visible device with locality-aware
    # placement and work stealing (SchedulerPool): auto = on when more
    # than one device is visible (single-device hosts size the pool to
    # 1, byte-identical to the shared device-0 queue); off = every
    # statement shares the device-0 queue (the PR 15 serving tier)
    "tidb_tpu_device_queues": "auto",
    # tables with at least this many rows partition their slab ranges
    # across the pool (one contiguous span per owner device) instead of
    # replicating a full copy per device (executor/device_cache.py)
    "tidb_tpu_partition_min_rows": DEFAULT_PARTITION_MIN_ROWS,
    # coalesced single-row ingest (session/writebatch.py): N queued
    # same-digest autocommit writes share ONE commit — readers pay one
    # delta extension instead of N; off = every write commits alone
    "tidb_tpu_write_coalesce": "on",
    # async compaction of delta-extended cache entries (executor/
    # delta.py): rebuild base slabs with re-chosen layouts in idle
    # batch-class slots, when the entry's own sizes say it is due
    # (delta.compaction_due); off = deltas accumulate until a test or a
    # tool drains them via delta.run_pending_compactions()
    "tidb_tpu_compaction": "on",
    # raises instead of answering from the host: a fragment that falls
    # back, and a host join/aggregate/sort over a device-sized scan
    # (executor/eligibility.check_strict_plan)
    "tidb_tpu_strict": "off",
    # rows of one device slab, the shape every slab program compiles for
    "tidb_tpu_max_slab_rows": DEFAULT_MAX_SLAB_ROWS,
    # the group capacity an aggregate without a reliable estimate starts
    # from (executor/agg_slabs.initial_group_cap; the ladder resizes)
    "tidb_tpu_group_cap": 1 << 16,
    # most rows an expand-mode join may put out in one batch (the HBM
    # guard; beyond it the tree runs in blocked passes)
    "tidb_tpu_join_out_cap": 1 << 26,
    # most (group, value) pairs one slab hands out for a DISTINCT
    # aggregate before the ladder resizes (clamped to the slab's rows)
    "tidb_tpu_distinct_pair_cap": 65536,
    # an aggregate over a join tree as one program a probe slab
    # (executor/agg_slabs.py); off = the mega-slab tree program
    "tidb_tpu_fused_pipeline": "on",
    # per-device byte budget of the table cache (executor/device_cache.py;
    # a v5e has 16 GiB, the rest is the programs' working set): beyond it
    # least-recently-used tables are evicted — the memory Tracker analog
    # for device residency (util/memory/tracker.go)
    "tidb_tpu_hbm_budget": 8 << 30,
    # shards of a distributed fragment: N pins an N-way mesh, 'auto' takes
    # every visible device, 0/1 = single device
    "tidb_tpu_dist_devices": 0,
    # rows of one hash-exchange bucket, overriding the balanced share
    # (skew and retry testing); 0 = derived from the estimate
    "tidb_tpu_exchange_bucket_cap": 0,
    # threads of the host hash aggregate's partial phase
    "tidb_tpu_cpu_concurrency": 1,
}


def _value(vars_: Mapping, name: str):
    v = vars_.get(name)
    return DEFAULT_VARS[name] if v is None else v


def is_on(v) -> bool:
    """An on/off value, MySQL-ish: 'off'/'false'/'0'/0/'' are off,
    anything else ('on', 'auto', 1, a path) is on."""
    if isinstance(v, str):
        return v.strip().lower() not in ("", "0", "off", "false")
    return bool(v)


def var_on(vars_: Mapping, name: str) -> bool:
    return is_on(_value(vars_, name))


def var_int(vars_: Mapping, name: str) -> int:
    return int(_value(vars_, name) or 0)


def var_str(vars_: Mapping, name: str) -> str:
    """As text, as it was set: a mode ('auto', 'on', …; the reader
    lowers it) or a path."""
    return str(_value(vars_, name))
