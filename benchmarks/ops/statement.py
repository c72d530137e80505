"""Operation kind `statement`: one SQL statement of the data set, sent over
the wire; the operation ends when the last row has been received, and it is
correct when those rows equal the reference's, in order.

    {"kind": "statement", "statement": "<name in the data set's STATEMENTS>"}

Every kind has these three functions. `bind` may draw parameters from `rng`
(a `numpy.random.Generator` seeded from --seed); this kind has none.
"""

from __future__ import annotations


def bind(spec: dict, dataset, rng) -> dict:
    """→ the bound operation: its `name`, and under `statements` every
    {name: SQL} it sends (warmed up once per connection, and read in the
    ledgers)."""
    name = spec["statement"]
    return {"name": name, "sql": dataset.STATEMENTS[name],
            "statements": {name: dataset.STATEMENTS[name]}}


def run(client, op: dict):
    """Send the operation; → its answer, kept for the comparison."""
    _names, rows = client.query(op["sql"])
    return rows


def check(op: dict, answer, reference: dict) -> bool:
    """Text equality with the reference's rows, order included (every
    statement with more than one row has an ORDER BY)."""
    return [tuple(r) for r in answer] == \
        [tuple(r) for r in reference[op["name"]]]
