"""The state layer: self time of the program's category `state` spans
(`state_load`: read and deserialize; `state_merge`; `state_save`:
serialize and write; analyzers/base.py calculate_metric) per verdict, ms.
"""

from benchmark.harness.spans import ms_per_call, of_category, self_seconds


def read(run):
    return ms_per_call(run, self_seconds(run.spans, of_category("state")))
