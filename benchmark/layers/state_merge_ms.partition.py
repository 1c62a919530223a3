"""Self time of the program's `state_merge` spans (the stored state merged
with the landing day's, analyzers/base.py calculate_metric) per verdict,
ms.
"""

from benchmark.harness.spans import ms_per_call, self_seconds


def read(run):
    return ms_per_call(run, self_seconds(run.spans, lambda s: s.name == "state_merge"))
