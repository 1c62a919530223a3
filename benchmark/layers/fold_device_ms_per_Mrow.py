"""Device busy time in the profiler trace (union of operation intervals in
the window, on the busiest device), ms per million rows.
"""

from benchmark.harness.spans import ms_per_mrow


def read(run):
    busy = max((w.busy_ns for w in (run.device or {}).values()), default=0)
    return ms_per_mrow(run, busy / 1e9) if busy > 0 else None
