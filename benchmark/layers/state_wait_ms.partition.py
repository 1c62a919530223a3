"""Self time of the program's `state_wait` spans on the calling thread
(the thread of the root spans): the caller blocked on the state layer's
batched loads and persists (deequ_tpu/runners/state_io.py), per verdict,
ms. None where no `state_wait` span opened: a run without a provider
that allows concurrent calls, or a program without the batches.
"""

from benchmark.harness.spans import ms_per_call, self_seconds


def read(run):
    caller = {r.tid for r in run.spans}
    return ms_per_call(run, self_seconds(
        run.spans, lambda s: s.name == "state_wait" and s.tid in caller))
