"""Self time of the program's `constraint` spans (checks/, constraints/)
per verdict, ms.
"""

from benchmark.harness.spans import ms_per_call, of_category, self_seconds


def read(run):
    return ms_per_call(run, self_seconds(run.spans, of_category("constraint")))
