"""Share of the HBM roofline, in %: the bytes the fused programs receive
(sum of the `wire_bytes` of `dispatch` spans, over the chips) at the
device's peak HBM bandwidth, over the busiest device's busy time. The
work is the bytes the programs receive, whatever computes them.
"""

from benchmark.harness.spans import attr_sum
from benchmark.trace.xplane import peaks


def read(run):
    if not run.device:
        return None
    wire = attr_sum(run.spans, lambda s: s.cat == "dispatch", "wire_bytes")
    busy = max(w.busy_ns for w in run.device.values()) / 1e9
    if not wire or busy <= 0:
        return None
    least = wire / run.chips / peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / busy
