"""The fold as the host sees it, host and device: self time of `dispatch`,
`transfer`, `host`, `native` and `merge` spans per verdict, ms.
"""

from benchmark.harness.spans import ms_per_call, of_category, self_seconds


def read(run):
    return ms_per_call(run, self_seconds(run.spans, of_category(
        "dispatch", "transfer", "host", "native", "merge")))
