"""Self time of the program's `group_encode` spans (analyzers/frequency.py:
each grouping column's dictionary encode and validity, per batch), ms per million rows.
"""

from benchmark.harness.spans import ms_per_mrow, self_seconds


def read(run):
    return ms_per_mrow(run, self_seconds(run.spans, lambda s: s.name == "group_encode"))
