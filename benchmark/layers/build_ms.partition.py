"""Self time of the program's `build` spans (ops/fused.py HostInputs: an
input array built from a batch, such as a predicate's mask or a sketch's
hash codes), all threads, per verdict, ms.
"""

from benchmark.harness.spans import ms_per_call, self_seconds


def read(run):
    return ms_per_call(run, self_seconds(run.spans, lambda s: s.name == "build"))
