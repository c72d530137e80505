"""The bytes a statement has to read from HBM, worked out by the benchmark.

`needed_bytes` is the numerator of `scan_hbm_share`: the physical (compressed)
resident bytes of the columns the statement names, as
`information_schema.table_storage` reports them, with the table whose slabs
zone maps can skip counted only for the share of its slabs that were
launched. It is cross-checked against the program's own `SCAN_BYTES` counter
on every traced run (printed on an earlier line) and in the rehearsal test.
"""

from __future__ import annotations


def storage_by_column(storage_rows) -> dict:
    """`table_storage` rows (dicts keyed by its column names) →
    {(table, column): (physical_bytes, zone_map_slabs)}."""
    return {(r["TABLE_NAME"], r["COLUMN_NAME"]):
            (int(r["PHYSICAL_BYTES"]), int(r["ZONE_MAP_SLABS"] or 0))
            for r in storage_rows}


def needed_bytes(columns: dict, pruned_table: str, storage: dict,
                 executions: int, slabs_skipped: int) -> int:
    """Bytes `executions` runs of one statement must read.

    columns: {table: [column, ...]} the statement names; storage: from
    `storage_by_column`; slabs_skipped: the statement's SLABS_SKIPPED delta
    over those executions (slabs of `pruned_table` that never dispatched).
    """
    total = 0.0
    for table, names in columns.items():
        for name in names:
            if (table, name) not in storage:
                raise KeyError(f"{table}.{name} is not resident: "
                               f"table_storage has {sorted(storage)}")
            phys, slabs = storage[(table, name)]
            share = 1.0
            if table == pruned_table and slabs and executions:
                share = 1.0 - slabs_skipped / (slabs * executions)
            total += phys * share * executions
    return int(round(total))
