"""Self time of the program's `group_merge` spans (analyzers/frequency.py,
streamed sources: each batch's groups merged into the accumulator, and
its finish), ms per million rows.
"""

from benchmark.harness.spans import ms_per_mrow, self_seconds


def read(run):
    return ms_per_mrow(run, self_seconds(run.spans, lambda s: s.name == "group_merge"))
