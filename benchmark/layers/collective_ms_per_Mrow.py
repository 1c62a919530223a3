"""Device time of collective operations in the profiler trace (the union
of a device's all-gather, all-reduce, reduce-scatter, all-to-all and
collective-permute intervals in the window; `trace.xplane.reduce`'s
`collective_ns`), on the device that spent the most in them, ms per
million rows. None without a device trace, or where no device ran a
collective (a program on one chip).
"""

from benchmark.harness.spans import ms_per_mrow


def read(run):
    most = max((w.collective_ns for w in (run.device or {}).values()), default=0)
    return ms_per_mrow(run, most / 1e9) if most > 0 else None
