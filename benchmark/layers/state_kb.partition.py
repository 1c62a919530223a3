"""State bytes read and written per verdict, KB (1,000 bytes): the
`bytes` attributes of the program's `state_load` and `state_save` spans
(the state provider's files, analyzers/state_provider.py).
"""

from benchmark.harness.spans import attr_sum


def read(run):
    total = attr_sum(run.spans, lambda s: s.name in ("state_load", "state_save"), "bytes")
    return None if total is None or not run.calls else total / 1e3 / len(run.calls)
