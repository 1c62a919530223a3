"""Self time of the program's `group_count` spans (analyzers/frequency.py:
the combined codes counted and the group keys gathered, per batch), ms per million rows.
"""

from benchmark.harness.spans import ms_per_mrow, self_seconds


def read(run):
    return ms_per_mrow(run, self_seconds(run.spans, lambda s: s.name == "group_count"))
