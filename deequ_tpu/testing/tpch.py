"""Seeded TPC-H `lineitem`-shaped table (BASELINE.json config 3).

Shared by `chip_smoke.py` and the Pallas kernel tests. The 16 columns, their types and
their value domains follow the TPC-H specification (§1.4.1 and §4.2.3):

* each order has 1-7 lines, numbered from 1, so (l_orderkey, l_linenumber)
  is a composite key; order keys are sparse (the first 8 of every 32);
* l_quantity in [1, 50], l_discount in [0.00, 0.10], l_tax in [0.00, 0.08];
* l_extendedprice = l_quantity * a part price in [900.00, 1099.99].

Deviations: dates are ISO strings drawn uniformly (~2.4k distinct) rather
than derived from the order date, and l_comment comes from a bounded
template dictionary (~2k distinct) instead of per-row text.

SF1 holds 6,001,215 rows (TPC-H §4.2.5).
"""

from __future__ import annotations

import numpy as np

SF1_ROWS = 6_001_215

_WORDS = np.array(
    ["carefully", "quickly", "furiously", "slyly", "blithely", "deposits",
     "requests", "packages", "theodolites", "accounts", "instructions",
     "foxes", "pinto beans", "ideas", "dependencies", "platelets"],
    dtype=object,
)


def lineitem_columns(n_rows: int, seed: int = 0) -> dict:
    """The lineitem columns as numpy arrays (string columns as object
    arrays); no nulls, as in TPC-H."""
    rng = np.random.default_rng(seed)
    n = n_rows
    # 1-7 lines per order; one spare order per 4 rows covers any draw
    lines = rng.integers(1, 8, n // 4 + 8)
    while int(lines.sum()) < n:
        lines = np.concatenate([lines, rng.integers(1, 8, n // 4 + 8)])
    order = np.repeat(np.arange(len(lines)), lines)[:n]
    starts = np.repeat(np.cumsum(lines) - lines, lines)[:n]
    linenumber = np.arange(n) - starts + 1
    orderkey = (order // 8) * 32 + order % 8 + 1

    days = np.array(
        [
            f"199{y}-{m:02d}-{d:02d}"
            for y in range(2, 9)
            for m in range(1, 13)
            for d in range(1, 29)
        ],
        dtype=object,
    )
    instruct = np.array(
        ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"],
        dtype=object,
    )
    modes = np.array(
        ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"], dtype=object
    )
    comments = np.array(
        [f"{a} {b} {c}" for a in _WORDS for b in _WORDS for c in _WORDS[:8]],
        dtype=object,
    )
    quantity = rng.integers(1, 51, n)
    part_price = rng.integers(90_000, 110_000, n) / 100.0
    return {
        "l_orderkey": orderkey,
        "l_partkey": rng.integers(1, 200_001, n),
        "l_suppkey": rng.integers(1, 10_001, n),
        "l_linenumber": linenumber,
        "l_quantity": quantity,
        "l_extendedprice": quantity * part_price,
        "l_discount": rng.integers(0, 11, n) / 100.0,
        "l_tax": rng.integers(0, 9, n) / 100.0,
        "l_returnflag": np.array(["A", "N", "R"], dtype=object)[
            rng.integers(0, 3, n)
        ],
        "l_linestatus": np.array(["O", "F"], dtype=object)[rng.integers(0, 2, n)],
        "l_shipdate": days[rng.integers(0, len(days), n)],
        "l_commitdate": days[rng.integers(0, len(days), n)],
        "l_receiptdate": days[rng.integers(0, len(days), n)],
        "l_shipinstruct": instruct[rng.integers(0, 4, n)],
        "l_shipmode": modes[rng.integers(0, 7, n)],
        "l_comment": comments[rng.integers(0, len(comments), n)],
    }


def build_lineitem_table(n_rows: int, seed: int = 0):
    """`lineitem_columns` as a deequ_tpu Table."""
    from deequ_tpu.data.table import Table

    return Table.from_numpy(lineitem_columns(n_rows, seed))
