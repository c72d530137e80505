"""Resumable DDL reorganization (ref: ddl/reorg.go:193 reorg watermark,
ddl/backfilling.go backfill workers).

In this engine, secondary indexes are lazy sorted snapshot views
(executor/index_scan.py), so the only eager cost of CREATE INDEX is the
UNIQUE validation scan — which at SF=10 scale touches 60M rows and used
to be all-or-nothing in one call. This module chunks it per storage
region: each region's sorted key run persists next to a tools.Checkpoint
(the same crash-resume marker backup/restore uses), so a backfill killed
mid-scan resumes after the last finished region instead of restarting
from zero — the single-process analog of the reference's reorg handle
persisting its next-key watermark into the job record.
"""

from __future__ import annotations

import os
from typing import List, Optional

import numpy as np

from tidb_tpu.errors import DuplicateKeyError
from tidb_tpu.executor.scan import align_chunk_to_schema


DEFAULT_REORG_BATCH = 1 << 16     # ddl/backfilling.go batch-size analog


def unique_backfill(session, info, cols: List[str], name: str,
                    ckpt_dir: Optional[str] = None) -> None:
    """Chunked CREATE UNIQUE INDEX validation over a pinned snapshot.

    Work splits into tidb_ddl_reorg_batch_size row batches. With
    `ckpt_dir` (session var tidb_ddl_reorg_checkpoint_dir), each batch's
    deduped key run is written to disk and marked in a Checkpoint AFTER
    it lands; a rerun skips finished batches and reloads their runs, so
    a killed backfill resumes after the last completed batch. The merge
    at the end catches duplicates that span batches. Raises
    DuplicateKeyError exactly like the reference's write-reorg dup check
    (ddl/backfilling.go)."""
    from tidb_tpu.session import _key_tuples
    from tidb_tpu.util import failpoint

    col_of = {c.name.lower(): i for i, c in enumerate(info.columns)}
    idxs = [col_of[c.lower()] for c in cols]
    snap = session._read_view_snapshot()
    if not snap.has_table(info.id):
        return None
    batch = int(session.vars.get("tidb_ddl_reorg_batch_size",
                                 DEFAULT_REORG_BATCH))
    ck = None
    if ckpt_dir:
        from tidb_tpu.tools import Checkpoint
        os.makedirs(ckpt_dir, exist_ok=True)
        ck = Checkpoint(os.path.join(ckpt_dir, f"reorg_{name}.json"),
                        op=f"create_index:{info.name}:{name}")

    def cleanup():
        if ck is not None:
            ck.finish()
            for pth in run_paths:
                if os.path.exists(pth):
                    os.remove(pth)

    runs: List[np.ndarray] = []
    run_paths: List[str] = []
    for i, (region, alive) in enumerate(snap.scan(info.id)):
        ch = None
        keys = None
        n_rows = region.chunk.num_rows
        n_alive = int(np.asarray(alive).sum())
        for b0 in range(0, n_rows, max(batch, 1)):
            b1 = min(b0 + max(batch, 1), n_rows)
            # the unit key fingerprints the region's LIVE row count too:
            # a delete between runs flips alive bits without changing
            # n_rows, and must invalidate the persisted run
            unit = f"part:{i}:{b0}:{n_rows}:{n_alive}"
            run_path = os.path.join(
                ckpt_dir, f"reorg_{name}.run{i}_{b0}.npy") \
                if ckpt_dir else None
            if ck is not None and ck.is_done(unit):
                runs.append(np.load(run_path, allow_pickle=True))
                run_paths.append(run_path)
                continue
            if keys is None:      # materialize the region lazily, once
                ch = align_chunk_to_schema(region.chunk, info)
                keys = _key_tuples(ch, idxs)
            live_keys = sorted(keys[ri] for ri in range(b0, b1)
                               if alive[ri] and keys[ri] is not None)
            for a, b in zip(live_keys, live_keys[1:]):
                if a == b:
                    # validation FAILED (not crashed): the job is over —
                    # drop the checkpoint so a later retry revalidates
                    # fresh data instead of replaying stale runs
                    cleanup()
                    raise DuplicateKeyError(
                        f"Duplicate entry {a!r} for key '{name}'")
            arr = np.empty(len(live_keys), dtype=object)
            arr[:] = live_keys
            if run_path:
                np.save(run_path, arr, allow_pickle=True)
                run_paths.append(run_path)
            runs.append(arr)
            if ck is not None:
                ck.mark(unit)
            # test seam: die between batches (the reorg.go:193 "owner
            # crash between batches" scenario) — the marked checkpoint
            # makes the NEXT run resume after this batch
            failpoint.inject("index-backfill")
    # cross-batch duplicates: merge the (already sorted) runs
    merged = sorted(k for run in runs for k in run)
    for a, b in zip(merged, merged[1:]):
        if a == b:
            cleanup()
            raise DuplicateKeyError(
                f"Duplicate entry {a!r} for key '{name}'")
    cleanup()
    # the TableData identity this pass validated — the caller loops
    # until it matches the live table (online-DDL quiescence check)
    return snap.table_data(info.id)
