"""Generated Parquet files, kept inside the checkout and keyed by
(configuration, rows, seed, generator version): the second run of a seed in a
checkout reads them back instead of writing them again. Writes are atomic
(a temporary name, then a rename), so a run cut short leaves no half file
that a later run would read."""

from __future__ import annotations

import os

CACHE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "cache")


def directory(config: str, rows: int, seed: int) -> str:
    from benchmark.data.tpch import VERSION

    path = os.path.join(CACHE, f"{config}-r{rows}-v{VERSION}-s{seed}")
    os.makedirs(path, exist_ok=True)
    return path


def parquet(path: str, make, row_group_size=None) -> bool:
    """Write `make()` (an Arrow table) to `path` unless it is there;
    True when it was written."""
    if os.path.exists(path):
        return False
    import pyarrow.parquet as pq

    tmp = f"{path}.tmp{os.getpid()}"
    pq.write_table(make(), tmp, row_group_size=row_group_size)
    os.replace(tmp, path)
    return True
