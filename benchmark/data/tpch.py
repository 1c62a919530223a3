"""Seeded TPC-H `lineitem`: the benchmark's own copy of the program's
`deequ_tpu/testing/tpch.py` (PR 21), so that no later PR can change the
data a cell measures by editing program code.

Same columns, domains and distributions as that generator (TPC-H §1.4.1, §4.2.3):
1-7 lines per order, numbered from 1, so (l_orderkey, l_linenumber) is a
composite key; sparse order keys (the first 8 of every 32); quantity in
[1, 50]; discount in [0.00, 0.10]; tax in [0.00, 0.08]; extended price =
quantity x a part price in [900.00, 1099.99]. Departures from dbgen, as in
the original: uniform ISO dates over 28-day months (2,352 days) and a
2,048-entry comment dictionary.

One change of form, none of content: string columns come back as
`Coded(codes, values)` (int32 codes into a small dictionary) instead of
6M-element object arrays, so the columns are made in seconds; the values
the codes name are the ones the original draws. What the program is handed
is set by `to_arrow`.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Union

import numpy as np

# bump on any change to what a seed generates: the data cache is keyed on it
VERSION = 1

# key domains at SF1 (TPC-H §4.2.5); a table at scale s has s times as many
SF1_ORDERS = 1_500_000
SF1_PARTS = 200_000
SF1_SUPPLIERS = 10_000

_WORDS = [
    "carefully", "quickly", "furiously", "slyly", "blithely", "deposits",
    "requests", "packages", "theodolites", "accounts", "instructions",
    "foxes", "pinto beans", "ideas", "dependencies", "platelets",
]
DAYS = np.array(
    [f"199{y}-{m:02d}-{d:02d}" for y in range(2, 9) for m in range(1, 13)
     for d in range(1, 29)],
    dtype=object,
)
RETURNFLAG = np.array(["A", "N", "R"], dtype=object)
LINESTATUS = np.array(["O", "F"], dtype=object)
INSTRUCT = np.array(
    ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"], dtype=object
)
MODES = np.array(
    ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"], dtype=object
)
COMMENTS = np.array(
    [f"{a} {b} {c}" for a in _WORDS for b in _WORDS for c in _WORDS[:8]],
    dtype=object,
)


class Coded(NamedTuple):
    """A string column: `values[codes]` are the rows."""

    codes: np.ndarray  # int32
    values: np.ndarray  # object array of str

    def __len__(self) -> int:
        return len(self.codes)


Columns = Dict[str, Union[np.ndarray, Coded]]


def _order_keys(order: np.ndarray) -> np.ndarray:
    return (order // 8) * 32 + order % 8 + 1


def _rest(rng, n: int, shipdate: np.ndarray, parts: int, suppliers: int) -> Columns:
    """Every column but the key pair; `shipdate` holds date codes."""
    partkey = rng.integers(1, parts + 1, n)
    suppkey = rng.integers(1, suppliers + 1, n)
    quantity = rng.integers(1, 51, n)
    part_price = rng.integers(90_000, 110_000, n) / 100.0
    return {
        "l_partkey": partkey,
        "l_suppkey": suppkey,
        "l_quantity": quantity,
        "l_extendedprice": quantity * part_price,
        "l_discount": rng.integers(0, 11, n) / 100.0,
        "l_tax": rng.integers(0, 9, n) / 100.0,
        "l_returnflag": Coded(rng.integers(0, 3, n, dtype=np.int32), RETURNFLAG),
        "l_linestatus": Coded(rng.integers(0, 2, n, dtype=np.int32), LINESTATUS),
        "l_shipdate": Coded(shipdate.astype(np.int32), DAYS),
        "l_commitdate": Coded(rng.integers(0, len(DAYS), n, dtype=np.int32), DAYS),
        "l_receiptdate": Coded(rng.integers(0, len(DAYS), n, dtype=np.int32), DAYS),
        "l_shipinstruct": Coded(rng.integers(0, 4, n, dtype=np.int32), INSTRUCT),
        "l_shipmode": Coded(rng.integers(0, 7, n, dtype=np.int32), MODES),
        "l_comment": Coded(
            rng.integers(0, len(COMMENTS), n, dtype=np.int32), COMMENTS
        ),
    }


_ORDER = (
    "l_orderkey", "l_partkey", "l_suppkey", "l_linenumber", "l_quantity",
    "l_extendedprice", "l_discount", "l_tax", "l_returnflag", "l_linestatus",
    "l_shipdate", "l_commitdate", "l_receiptdate", "l_shipinstruct",
    "l_shipmode", "l_comment",
)


def lineitem(n_rows: int, seed: int, scale: float = 1.0) -> Columns:
    """The whole table: `n_rows` rows; key domains at scale factor
    `scale`. No nulls, as in TPC-H."""
    rng = np.random.default_rng(seed)
    n = n_rows
    lines = rng.integers(1, 8, n // 4 + 8)
    while int(lines.sum()) < n:
        lines = np.concatenate([lines, rng.integers(1, 8, n // 4 + 8)])
    order = np.repeat(np.arange(len(lines)), lines)[:n]
    starts = np.repeat(np.cumsum(lines) - lines, lines)[:n]
    out: Columns = {
        "l_orderkey": _order_keys(order),
        "l_linenumber": np.arange(n) - starts + 1,
    }
    shipdate = rng.integers(0, len(DAYS), n)
    out.update(
        _rest(rng, n, shipdate, int(SF1_PARTS * scale), int(SF1_SUPPLIERS * scale))
    )
    return {k: out[k] for k in _ORDER}


def day_sizes(total_rows: int, pool: int) -> np.ndarray:
    """Rows landing on each of `pool` consecutive ship dates, binomial
    around total/days as uniform ship dates give. Drawn from a fixed
    stream, so every seed verifies the same sizes (the seed changes
    content, never the amount of work)."""
    rng = np.random.default_rng(0x5EED_DA75)
    return rng.binomial(total_rows, 1.0 / len(DAYS), pool)


def lineitem_day(n_rows: int, seed: int, day: int, scale: float) -> Columns:
    """The `n_rows` lineitem rows shipped on date `DAYS[day]` of a table at
    scale factor `scale`: order keys drawn from the whole order domain
    (a day's lines belong to orders all over the table), line numbers
    1-7."""
    rng = np.random.default_rng([seed, day])
    n = n_rows
    order = rng.integers(0, int(SF1_ORDERS * scale), n)
    out: Columns = {
        "l_orderkey": _order_keys(order),
        "l_linenumber": rng.integers(1, 8, n),
    }
    out.update(
        _rest(
            rng, n, np.full(n, day % len(DAYS)),
            int(SF1_PARTS * scale), int(SF1_SUPPLIERS * scale),
        )
    )
    return {k: out[k] for k in _ORDER}


def to_arrow(cols: Columns, dictionary: bool = True):
    """The columns as an Arrow table. With `dictionary`, strings are
    dictionary arrays: what the Parquet files are written from, their
    pages dictionary-encoded as a default writer makes them. Without,
    strings are plain `string` arrays, as `pyarrow.parquet.read_table`
    or `pyarrow.array` hand a user's strings over: the in-memory table."""
    import pyarrow as pa

    arrays = {}
    for name, col in cols.items():
        if isinstance(col, Coded):
            arr = pa.DictionaryArray.from_arrays(
                pa.array(col.codes, pa.int32()), pa.array(list(col.values), pa.string())
            )
            arrays[name] = arr if dictionary else arr.dictionary_decode()
        else:
            arrays[name] = pa.array(col)
    return pa.table(arrays)
