"""Self time of the program's `transfer` spans (ops/fused.py _fold: the
host blocked on the device's results, plus the D2H copy), ms per million
rows.
"""

from benchmark.harness.spans import ms_per_mrow, of_category, self_seconds


def read(run):
    return ms_per_mrow(run, self_seconds(run.spans, of_category("transfer")))
