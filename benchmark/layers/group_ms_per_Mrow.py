"""Self time of the program's `group` spans (frequency pass:
analyzers/frequency.py, ops/freq_agg.py), ms per million rows verified.
"""

from benchmark.harness.spans import ms_per_mrow, of_category, self_seconds


def read(run):
    return ms_per_mrow(run, self_seconds(run.spans, of_category("group")))
