"""Self time of the program's `host` and `native` spans (host members of
ops/fused.py, ops/native/), ms per million rows.
"""

from benchmark.harness.spans import ms_per_mrow, of_category, self_seconds


def read(run):
    return ms_per_mrow(run, self_seconds(run.spans, of_category("host", "native")))
