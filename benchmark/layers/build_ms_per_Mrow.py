"""Self time of the program's `build` spans (ops/fused.py HostInputs: an
input array built from a batch), all threads, ms per million rows.
"""

from benchmark.harness.spans import ms_per_mrow, self_seconds


def read(run):
    return ms_per_mrow(run, self_seconds(run.spans, lambda s: s.name == "build"))
