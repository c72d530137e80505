"""Ecosystem tools: logical dump, binary backup/restore, CSV import/export.

The reference ships these as in-repo CLIs (SURVEY §2.5): **dumpling**
(logical SQL dump over a MySQL connection), **BR** (physical backup /
restore with resumable checkpoints, br/pkg/{backup,restore,task}), and
**lightning** (bulk file import with checkpoints,
br/pkg/lightning/checkpoints/). The TPU-first engine stores tables as
immutable columnar regions, so the physical format here is the Chunk wire
codec (tidb_tpu/chunk/codec.py — the same Arrow-shaped layout the device
marshalling uses) plus a JSON schema sidecar.

Checkpoint discipline (BR + lightning checkpoints; also the repo's
checkpoint/resume answer to ddl/reorg.go's resumable backfill): every
table lands atomically (tmp file + rename) and is then recorded in
`checkpoint.json`; a re-run of the same operation skips recorded tables,
so a crash mid-way resumes instead of restarting.
"""

from __future__ import annotations

import datetime
import json
import os
from typing import Dict, List, Optional, Sequence

import numpy as np

from tidb_tpu.chunk import Chunk
from tidb_tpu.chunk.codec import decode_chunk, encode_chunk
from tidb_tpu.errors import TiDBTPUError
from tidb_tpu.executor.scan import align_chunk_to_schema

BACKUP_FORMAT_VERSION = 1


# ---------------------------------------------------------------------------
# Checkpoints (ref: br/pkg/lightning/checkpoints, ddl/reorg.go handles)
# ---------------------------------------------------------------------------


class Checkpoint:
    """Crash-resumable progress marker: a JSON set of finished units."""

    def __init__(self, path: str, op: str):
        self.path = path
        self.op = op
        self.done: List[str] = []
        if os.path.exists(path):
            with open(path) as f:
                data = json.load(f)
            if data.get("op") != op:
                raise TiDBTPUError(
                    f"checkpoint at {path} belongs to a different "
                    f"operation ({data.get('op')!r}, not {op!r})")
            self.done = list(data.get("done", []))

    def is_done(self, unit: str) -> bool:
        return unit in self.done

    def mark(self, unit: str) -> None:
        self.done.append(unit)
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"op": self.op, "done": self.done}, f)
        os.replace(tmp, self.path)

    def finish(self) -> None:
        if os.path.exists(self.path):
            os.remove(self.path)


def _atomic_write(path: str, data: bytes) -> None:
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(data)
    os.replace(tmp, path)


# ---------------------------------------------------------------------------
# DDL regeneration (shared by dump + backup metadata)
# ---------------------------------------------------------------------------


def create_table_sql(info) -> str:
    cols = []
    for c in info.columns:
        spec = f"`{c.name}` {c.ftype}"
        if getattr(c, "auto_increment", False):
            spec += " AUTO_INCREMENT"
        if not c.ftype.nullable and not c.primary_key:
            spec += " NOT NULL"
        cols.append(spec)
    if info.primary_key:
        cols.append("PRIMARY KEY (" +
                    ", ".join(f"`{c}`" for c in info.primary_key) + ")")
    ddl = f"CREATE TABLE `{info.name}` (\n  " + ",\n  ".join(cols) + "\n)"
    p = getattr(info, "partition", None)
    if p is not None:
        if p.kind == "hash":
            ddl += (f"\nPARTITION BY HASH (`{p.column}`) "
                    f"PARTITIONS {p.num}")
        else:
            ft = info.columns[p.col_offset].ftype
            defs = []
            for name, b in zip(p.names, p.bounds):
                if b is None:
                    lit = "MAXVALUE"
                else:
                    val = ft.decode_value(b)
                    lit = (str(val) if isinstance(val, (int, float))
                           else "'" + str(val) + "'")
                defs.append(f"PARTITION `{name}` VALUES LESS THAN ({lit})")
            ddl += (f"\nPARTITION BY RANGE (`{p.column}`) (\n  " +
                    ",\n  ".join(defs) + "\n)")
    extra = []
    for ix in info.indexes:
        u = "UNIQUE " if ix.unique else ""
        extra.append(f"CREATE {u}INDEX `{ix.name}` ON `{info.name}` (" +
                     ", ".join(f"`{c}`" for c in ix.columns) + ")")
    return ";\n".join([ddl] + extra) + ";"


def _sql_literal(v) -> str:
    if v is None:
        return "NULL"
    if isinstance(v, bool):
        return "1" if v else "0"
    if isinstance(v, (int, float)):
        return repr(v)
    if isinstance(v, (datetime.date, datetime.datetime)):
        return f"'{v}'"
    s = str(v).replace("\\", "\\\\").replace("'", "\\'")
    return f"'{s}'"


# ---------------------------------------------------------------------------
# dumpling — logical SQL dump over a connection or in-process session
# ---------------------------------------------------------------------------


def dump_sql(source, out_dir: str, tables: Optional[Sequence[str]] = None,
             rows_per_insert: int = 1000) -> List[str]:
    """Write `<table>-schema.sql` + `<table>.sql` per table (dumpling's
    file layout). `source` is anything with .query(sql) returning rows —
    a tidb_tpu.client.Client (over the wire) or a Session."""
    os.makedirs(out_dir, exist_ok=True)
    ckpt = Checkpoint(os.path.join(out_dir, "checkpoint.json"), "dump")
    names = _table_names(source, tables)
    written = []
    for t in names:
        if ckpt.is_done(t):
            continue
        ddl = _show_create(source, t)
        _atomic_write(os.path.join(out_dir, f"{t}-schema.sql"),
                      (ddl.rstrip(";\n ") + ";\n").encode())
        rows = _query_rows(source, f"SELECT * FROM `{t}`")
        lines = []
        for start in range(0, len(rows), rows_per_insert):
            batch = rows[start:start + rows_per_insert]
            vals = ",\n".join(
                "(" + ", ".join(_sql_literal(v) for v in r) + ")"
                for r in batch)
            lines.append(f"INSERT INTO `{t}` VALUES\n{vals};")
        _atomic_write(os.path.join(out_dir, f"{t}.sql"),
                      ("\n".join(lines) + "\n").encode())
        ckpt.mark(t)
        written.append(t)
    ckpt.finish()
    return written


def load_dump(session, dump_dir: str) -> List[str]:
    """Replay a dump directory into a session (schema files first)."""
    files = sorted(os.listdir(dump_dir))
    loaded = []
    for f in files:
        if f.endswith("-schema.sql"):
            session.execute(open(os.path.join(dump_dir, f)).read())
            loaded.append(f)
    for f in files:
        if f.endswith(".sql") and not f.endswith("-schema.sql"):
            sql = open(os.path.join(dump_dir, f)).read().strip()
            if sql:
                session.execute(sql)
            loaded.append(f)
    return loaded


# ---------------------------------------------------------------------------
# BR — physical backup/restore of the columnar store
# ---------------------------------------------------------------------------


def backup(engine, out_dir: str,
           tables: Optional[Sequence[str]] = None) -> List[str]:
    """Physical backup: per table, a JSON schema sidecar + the live rows
    as Chunk-codec payloads (ref: br/pkg/backup; the payload format is
    the engine's own wire codec, SURVEY A.1). Resumable via checkpoint."""
    os.makedirs(out_dir, exist_ok=True)
    ckpt = Checkpoint(os.path.join(out_dir, "checkpoint.json"), "backup")
    snap = engine.store.snapshot()
    infos = [t for t in engine.catalog.info_schema.list_tables()
             if not t.name.startswith("#")]
    if tables is not None:
        want = {t.lower() for t in tables}
        infos = [t for t in infos if t.name.lower() in want]
    done = []
    for info in infos:
        if ckpt.is_done(info.name):
            continue
        from tidb_tpu.util import failpoint
        failpoint.inject("backup-table")
        payloads = []
        if snap.has_table(info.id):
            for region, alive in snap.scan(info.id):
                chunk = align_chunk_to_schema(region.chunk, info)
                if not alive.all():
                    chunk = chunk.take(np.nonzero(alive)[0])
                if chunk.num_rows:
                    payloads.append(encode_chunk(chunk))
        meta = {
            "version": BACKUP_FORMAT_VERSION,
            "name": info.name,
            "ddl": create_table_sql(info),
            "n_chunks": len(payloads),
        }
        body = b"".join(
            len(p).to_bytes(8, "little") + p for p in payloads)
        _atomic_write(os.path.join(out_dir, f"{info.name}.meta.json"),
                      json.dumps(meta).encode())
        _atomic_write(os.path.join(out_dir, f"{info.name}.chunks"), body)
        ckpt.mark(info.name)
        done.append(info.name)
    # system state: SET GLOBAL variables + users/grants — the
    # mysql.global_variables / mysql.user tables' analog, so both
    # survive a restore-into-a-fresh-engine "restart"
    with engine.stats_lock:
        gvars = dict(engine.global_vars)
    sys_state = {"global_vars": gvars,
                 "auth": engine.auth.dump_state()}
    _atomic_write(os.path.join(out_dir, "system.meta.json"),
                  json.dumps(sys_state).encode())
    ckpt.finish()
    return done


def restore(engine, backup_dir: str) -> List[str]:
    """Recreate tables + data from a backup directory; resumable (a table
    already restored — recorded in the restore checkpoint — is skipped)."""
    ckpt = Checkpoint(os.path.join(backup_dir, "restore.checkpoint.json"),
                      "restore")
    session = engine.new_session()
    restored = []
    sys_path = os.path.join(backup_dir, "system.meta.json")
    if os.path.exists(sys_path):
        with open(sys_path) as f:
            sys_state = json.load(f)
        with engine.stats_lock:
            engine.global_vars.update(sys_state.get("global_vars", {}))
        if sys_state.get("auth"):
            engine.auth.load_state(sys_state["auth"])
    metas = sorted(f for f in os.listdir(backup_dir)
                   if f.endswith(".meta.json") and f != "system.meta.json")
    for mf in metas:
        with open(os.path.join(backup_dir, mf)) as f:
            meta = json.load(f)
        name = meta["name"]
        if ckpt.is_done(name):
            continue
        if meta.get("version", 0) > BACKUP_FORMAT_VERSION:
            raise TiDBTPUError(
                f"backup of {name} uses a newer format "
                f"({meta['version']} > {BACKUP_FORMAT_VERSION})")
        from tidb_tpu.util import failpoint
        failpoint.inject("restore-table")
        session.execute(meta["ddl"])
        info = engine.catalog.info_schema.table(name)
        ftypes = [c.ftype for c in info.columns]
        path = os.path.join(backup_dir, f"{name}.chunks")
        buf = open(path, "rb").read() if os.path.exists(path) else b""
        pos = 0
        txn = engine.store.begin()
        while pos < len(buf):
            ln = int.from_bytes(buf[pos:pos + 8], "little")
            pos += 8
            chunk = decode_chunk(buf[pos:pos + ln], ftypes)
            pos += ln
            if info.partition is not None:
                # restored rows must re-acquire their region partition
                # tags or partition DDL/pruning would miss them
                from tidb_tpu.planner.partition import split_chunk
                for ordinal, sub in split_chunk(info.partition, chunk):
                    txn.append(info.id, sub, part=ordinal)
            else:
                txn.append(info.id, chunk)
        txn.commit()
        ckpt.mark(name)
        restored.append(name)
    ckpt.finish()
    return restored


# ---------------------------------------------------------------------------
# CSV import/export (lightning-lite)
# ---------------------------------------------------------------------------


def export_csv(source, table: str, path: str, delimiter: str = ",") -> int:
    import csv
    names, rows = _query_cols_rows(source, f"SELECT * FROM `{table}`")
    with open(path, "w", newline="") as f:
        w = csv.writer(f, delimiter=delimiter)
        w.writerow(names)
        for r in rows:
            w.writerow(["\\N" if v is None else v for v in r])
    return len(rows)


def import_csv(session, table: str, path: str, delimiter: str = ",",
               batch_rows: int = 2000) -> int:
    """Bulk CSV load through the SQL layer (lightning's logical mode);
    the header row must name the columns."""
    import csv
    total = 0
    with open(path, newline="") as f:
        r = csv.reader(f, delimiter=delimiter)
        header = next(r)
        cols = ", ".join(f"`{c}`" for c in header)
        batch: List[str] = []
        for row in r:
            vals = ", ".join(
                "NULL" if v == "\\N" else _sql_literal(v) for v in row)
            batch.append(f"({vals})")
            if len(batch) >= batch_rows:
                session.execute(
                    f"INSERT INTO `{table}` ({cols}) VALUES " +
                    ",".join(batch))
                total += len(batch)
                batch = []
        if batch:
            session.execute(f"INSERT INTO `{table}` ({cols}) VALUES " +
                            ",".join(batch))
            total += len(batch)
    return total


# ---------------------------------------------------------------------------
# source adapters (Client vs Session)
# ---------------------------------------------------------------------------


def _table_names(source, tables) -> List[str]:
    if tables is not None:
        return list(tables)
    if hasattr(source, "engine"):            # Session
        return [t.name for t in
                source.engine.catalog.info_schema.list_tables()
                if not t.name.startswith("#")]
    _, rows = source.query("SHOW TABLES")
    return [r[0] for r in rows]


def _show_create(source, table: str) -> str:
    if hasattr(source, "engine"):
        info = source.engine.catalog.info_schema.table(table)
        return create_table_sql(info)
    _, rows = source.query(f"SHOW CREATE TABLE `{table}`")
    return rows[0][1]


def _query_rows(source, sql: str):
    if hasattr(source, "engine"):
        return source.query(sql).rows
    _, rows = source.query(sql)
    return rows


def _query_cols_rows(source, sql: str):
    if hasattr(source, "engine"):
        rs = source.query(sql)
        return rs.names, rs.rows
    return source.query(sql)
