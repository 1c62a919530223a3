"""The service-level DQ4xx codes (ISSUE 14).

The runtime error codes split in two: `core/controller.py` owns the
codes a RUN ends with (DQ401-DQ407 — cancelled, deadline, stalled,
preempted, quota-at-boundary, drain), while this module owns the codes
a SUBMISSION is turned away with before or instead of running:

  * DQ410 — rejected at admission: the EXPLAIN-first gate proved the
    submission should never reach a worker (the plan can never fit the
    tenant's quota window — the DQ319 lint — or admission itself
    failed);
  * DQ411 — quota exceeded at admission: the tenant is at its
    concurrent/pending-run budget or its state-repository disk budget
    (the mid-run variant, tripped at a partition boundary, is the
    controller's DQ406);
  * DQ412 — shed on overload: the tier queue was saturated and this
    submission (or the queued one it displaced) lost the
    priority/deadline comparison, or its deadline expired while
    queued;
  * DQ413 — circuit breaker open: the (tenant, dataset) pair has
    repeatedly failed its runs and is fenced off from the pool until
    the cooldown's half-open probe succeeds;
  * DQ414 — drained: the service was asked to shut down (SIGTERM /
    close()) and returned this queued submission unrun; resubmit after
    restart — any partition states earlier attempts committed still
    resume.
"""

from __future__ import annotations

DQ_REJECTED = "DQ410"
DQ_QUOTA_EXCEEDED = "DQ411"
DQ_SHED = "DQ412"
DQ_BREAKER_OPEN = "DQ413"
DQ_DRAINED = "DQ414"

#: code -> one-line meaning, for operator-facing rendering
CODE_MEANINGS = {
    DQ_REJECTED: "rejected at admission (EXPLAIN-first gate)",
    DQ_QUOTA_EXCEEDED: "tenant quota exceeded at admission",
    DQ_SHED: "shed on overload (priority/deadline)",
    DQ_BREAKER_OPEN: "circuit breaker open for (tenant, dataset)",
    DQ_DRAINED: "returned unrun by a graceful drain",
}

__all__ = [
    "CODE_MEANINGS",
    "DQ_BREAKER_OPEN",
    "DQ_DRAINED",
    "DQ_QUOTA_EXCEEDED",
    "DQ_REJECTED",
    "DQ_SHED",
]
