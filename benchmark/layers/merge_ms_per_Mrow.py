"""Self time of the program's `merge` spans (ops/fused.py `_fold`: device
results merged into the running aggregates and finished on the host;
runners' state merge), ms per million rows.
"""

from benchmark.harness.spans import ms_per_mrow, of_category, self_seconds


def read(run):
    return ms_per_mrow(run, self_seconds(run.spans, of_category("merge")))
