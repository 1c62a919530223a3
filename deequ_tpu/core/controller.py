"""Cooperative run control: cancel tokens, deadlines, bounded retry,
and the stall watchdog (ISSUE 13 tentpole).

A `RunController` threads suite -> runner -> fused scan and is honored
at batch granularity: the fold loops probe `controller.check()` between
batches (an attribute check when no controller is attached), and a
tripped check raises `RunCancelled` carrying the run's progress. The
raise unwinds through `contextlib.closing` around the staged pipeline
and the source's `batches()` generator, so every stage thread, decode
worker, readahead slot and file descriptor joins through the SAME
shutdown contract an exhausted scan uses (pinned by
tests/test_pipeline_shutdown.py) — cancellation is just an early exit,
not a second teardown path.

All clock reads live here (core/), keeping the TIMING lint's ban on
ad-hoc timing in ops/ and runners/ intact: those layers call
`check()` / `beat()` and never read a clock themselves.

The DQ4xx runtime error codes (plan-time lints own DQ1xx-DQ3xx):

  * DQ401 — run cancelled by an explicit `cancel()`;
  * DQ402 — run deadline exceeded;
  * DQ403 — reserved: a retry budget exhausted WITHOUT a degrade path
    (every current retry site degrades to the pyarrow fallback instead,
    counted in `engine.retry.*` telemetry — never a wrong answer);
  * DQ404 — run stalled: the watchdog saw no batch progress for the
    stall window and cancelled the run after dumping per-stage state.

Service-era additions (ISSUE 14): the `DQService` scheduler needs to
stop a run WITHOUT losing its committed partition states, so the
controller also carries a *soft* cancel — `cancel_at_boundary()` —
that trips only at checks marked `boundary=True` (the partition
boundaries in `FusedScanPass._run_partitioned`, where every finished
partition has already committed to the StateRepository). In-flight
batches keep folding until the current partition lands; the raise then
unwinds through the same closing() shutdown contract a hard cancel
uses. Reasons/codes for the soft path:

  * DQ405 — run preempted: the scheduler evicted a heavy run so a
    cheaper one could take its worker; the submission is requeued and
    its resume loads the committed partitions (bit-identical — pinned
    by tests/test_service.py);
  * DQ406 — run stopped at a partition boundary because the tenant's
    scan-bytes/disk quota ran out mid-run (admission-time quota
    rejections are the service's DQ411);
  * DQ407 — run stopped by a graceful drain (SIGTERM): the partition
    in flight committed, the rest resumes after restart.

A `boundary_probe` hook — set by the service — runs at every boundary
check with the run's progress dict and may return a soft-cancel reason
(the per-partition quota-charging seam).
"""

from __future__ import annotations

import os
import random
import sys
import threading
import time
import traceback
from typing import Any, Callable, Dict, Optional, Tuple

DQ_CANCELLED = "DQ401"
DQ_DEADLINE = "DQ402"
DQ_RETRIES_EXHAUSTED = "DQ403"  # reserved — see module docstring
DQ_STALLED = "DQ404"
DQ_PREEMPTED = "DQ405"
DQ_QUOTA = "DQ406"
DQ_DRAIN = "DQ407"

_REASON_CODES = {
    "cancelled": DQ_CANCELLED,
    "deadline": DQ_DEADLINE,
    "stalled": DQ_STALLED,
    "preempted": DQ_PREEMPTED,
    "quota": DQ_QUOTA,
    "drain": DQ_DRAIN,
}

#: soft-cancel reasons: these trip only at `boundary=True` checks, so
#: the partition in flight commits its states before the run unwinds
SOFT_REASONS = frozenset({"preempted", "quota", "drain"})


class RunCancelled(RuntimeError):
    """A run ended early on purpose: explicit cancel, deadline, or the
    stall watchdog. Carries the DQ4xx code and a progress dict (batches
    and — for partitioned runs — partitions completed), so the caller
    knows exactly what a rerun will resume from: every partition
    committed to the StateRepository before the cancel loads from cache
    instead of rescanning."""

    def __init__(
        self,
        reason: str,
        *,
        where: str = "",
        progress: Optional[Dict[str, Any]] = None,
    ) -> None:
        self.reason = reason
        self.code = _REASON_CODES.get(reason, DQ_CANCELLED)
        self.where = where
        self.progress = dict(progress or {})
        detail = f" at {where}" if where else ""
        extra = ""
        if self.progress:
            extra = " (" + ", ".join(
                f"{k}={v}" for k, v in sorted(self.progress.items())
            ) + ")"
        super().__init__(f"[{self.code}] run {reason}{detail}{extra}")


class RunController:
    """Cooperative cancel token + optional deadline for one run.

    Thread-safe: any thread may `cancel()`; the run's fold loop calls
    `check()` between batches and raises `RunCancelled` once tripped.
    `beat()` is the watchdog's liveness signal — one call per folded
    batch, a plain int increment on the fold thread."""

    def __init__(self, deadline_s: Optional[float] = None) -> None:
        self.deadline_s = float(deadline_s) if deadline_s is not None else None
        self._deadline_at = (
            time.monotonic() + self.deadline_s
            if self.deadline_s is not None
            else None
        )
        self._cancel = threading.Event()
        self._reason: str = "cancelled"
        self._soft_cancel = threading.Event()
        self._soft_reason: str = "preempted"
        self._boundary_probe: Optional[
            Callable[[Dict[str, Any]], Optional[str]]
        ] = None
        self.beats = 0

    def cancel(self, reason: str = "cancelled") -> None:
        """Trip the token; the run raises RunCancelled at its next
        check. First cancel wins the reason."""
        if not self._cancel.is_set():
            self._reason = reason
            self._cancel.set()

    def cancel_at_boundary(self, reason: str = "preempted") -> None:
        """Soft cancel: trip the run at its next `boundary=True` check
        only — batch-granularity checks pass through, so the partition
        in flight finishes and commits its states before the raise.
        First soft cancel wins the reason; a hard `cancel()` still
        overrides everywhere."""
        if not self._soft_cancel.is_set():
            self._soft_reason = reason
            self._soft_cancel.set()

    def set_boundary_probe(
        self, probe: Optional[Callable[[Dict[str, Any]], Optional[str]]]
    ) -> None:
        """Install a hook run at every boundary check with the progress
        dict; a non-None return soft-cancels with that reason. The
        service charges per-partition quota usage through it."""
        self._boundary_probe = probe

    def bind_shared_cancel(self, token: "SharedCancelToken") -> None:
        """Chain a cross-process `SharedCancelToken` into the boundary
        probe: a token tripped by ANY shard (or the launcher) cancels
        this run at its next partition boundary — the partition in
        flight commits first, exactly like a service preemption. An
        existing probe keeps running and wins on a non-None reason."""
        prev = self._boundary_probe

        def probe(progress: Dict[str, Any]) -> Optional[str]:
            if prev is not None:
                reason = prev(progress)
                if reason:
                    return reason
            return token.reason()

        self._boundary_probe = probe

    @property
    def cancelled(self) -> bool:
        return self._cancel.is_set()

    @property
    def soft_cancelled(self) -> bool:
        return self._soft_cancel.is_set()

    def remaining_s(self) -> Optional[float]:
        """Seconds until the deadline, or None when none is set."""
        if self._deadline_at is None:
            return None
        return self._deadline_at - time.monotonic()

    def beat(self) -> None:
        """One unit of forward progress (a folded batch): feeds the
        stall watchdog. Single-writer (the fold thread)."""
        self.beats += 1

    def check(
        self,
        where: str = "",
        progress: Optional[Dict[str, Any]] = None,
        *,
        boundary: bool = False,
    ) -> None:
        """Raise RunCancelled when cancelled or past the deadline.
        `boundary=True` marks a resume point (a partition boundary:
        everything before it has committed): only there do soft cancels
        trip and the boundary probe run."""
        if self._cancel.is_set():
            raise RunCancelled(self._reason, where=where, progress=progress)
        if self._deadline_at is not None and time.monotonic() > self._deadline_at:
            self._reason = "deadline"
            self._cancel.set()
            raise RunCancelled("deadline", where=where, progress=progress)
        if boundary:
            probe = self._boundary_probe
            if probe is not None:
                reason = probe(dict(progress or {}))
                if reason:
                    self.cancel_at_boundary(reason)
            if self._soft_cancel.is_set():
                raise RunCancelled(
                    self._soft_reason, where=where, progress=progress
                )


class SharedCancelToken:
    """Cross-process boundary-cancel rendezvous for the sharded scan
    (parallel/multihost.py): one file on a filesystem every shard can
    see. Tripping publishes a reason atomically (tmp + rename); every
    shard's boundary probe (`RunController.bind_shared_cancel`) polls
    `reason()` at its partition boundaries — a stat of one path, no
    collective, so a cancel propagates without waiting for the next
    allgather. First trip effectively wins (a near-simultaneous second
    trip may overwrite the reason; ANY published reason cancels).

    All failure modes degrade to "not tripped": a token on a vanished
    directory simply never fires, it cannot wedge or crash a run."""

    def __init__(self, path: str) -> None:
        self.path = str(path)

    def trip(self, reason: str = "cancelled") -> None:
        if os.path.exists(self.path):
            return
        tmp = f"{self.path}.{os.getpid()}.tmp"
        try:
            with open(tmp, "w", encoding="utf-8") as handle:
                handle.write(reason)
            os.replace(tmp, self.path)
        except OSError:  # fault-ok: a failed trip = not tripped
            try:
                os.unlink(tmp)
            except OSError:
                pass

    def reason(self) -> Optional[str]:
        """The published cancel reason, or None while untripped. An
        empty or unreadable file reads as a plain "cancelled"."""
        if not os.path.exists(self.path):
            return None
        try:
            with open(self.path, encoding="utf-8") as handle:
                text = handle.read().strip()
        except OSError:
            return "cancelled"
        return text or "cancelled"

    @property
    def tripped(self) -> bool:
        return self.reason() is not None


class StallWatchdog:
    """Heartbeat-driven stall detector: a timer thread that watches the
    controller's beat counter. One full window with no beat dumps
    per-stage state (the live heartbeat snapshot when one is running,
    else the deequ-* thread stacks) to stderr; a second consecutive
    silent window cancels the run with reason "stalled" (DQ404), so the
    wedged scan fails with forensics instead of hanging forever.

    The dump-then-cancel split is deliberate: a slow batch that recovers
    costs one diagnostic dump, not the run."""

    def __init__(
        self,
        controller: RunController,
        timeout_s: float,
        *,
        out=None,
        snapshot_fn: Optional[Callable[[], Any]] = None,
    ) -> None:
        self.controller = controller
        self.timeout_s = float(timeout_s)
        self.dumps = 0
        self._out = out if out is not None else sys.stderr
        self._snapshot_fn = snapshot_fn
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "StallWatchdog":
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="deequ-watchdog"
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None

    def _run(self) -> None:
        last = self.controller.beats
        silent_windows = 0
        while not self._stop.wait(self.timeout_s):
            now = self.controller.beats
            if now != last:
                last = now
                silent_windows = 0
                continue
            silent_windows += 1
            self._dump(now, silent_windows)
            if silent_windows >= 2:
                self.controller.cancel("stalled")
                return

    def _dump(self, beats: int, silent_windows: int) -> None:
        self.dumps += 1
        lines = [
            f"deequ-watchdog: no batch progress for "
            f"{silent_windows * self.timeout_s:g}s "
            f"(beats={beats}, window={self.timeout_s:g}s)"
        ]
        snap = None
        if self._snapshot_fn is not None:
            try:
                snap = self._snapshot_fn()
            except Exception:  # noqa: BLE001 — diagnostics must not kill the run
                snap = None
        if snap:
            lines.append(f"deequ-watchdog: stage state: {snap}")
        else:
            lines.extend(_engine_thread_stacks())
        try:
            self._out.write("\n".join(lines) + "\n")
            self._out.flush()
        except Exception:  # noqa: BLE001
            pass


def _engine_thread_stacks(prefix: str = "deequ-") -> list:
    """One-line-per-frame stacks of the engine's worker threads — the
    per-stage state dump when no heartbeat snapshot is live."""
    frames = sys._current_frames()
    lines = []
    for t in threading.enumerate():
        if not t.name.startswith(prefix) or t.name == "deequ-watchdog":
            continue
        frame = frames.get(t.ident)
        if frame is None:
            continue
        stack = traceback.extract_stack(frame)
        tail = stack[-1] if stack else None
        where = f"{tail.filename}:{tail.lineno} {tail.name}" if tail else "?"
        lines.append(f"deequ-watchdog:   {t.name} @ {where}")
    return lines or ["deequ-watchdog:   (no engine worker threads alive)"]


def backoff_s(base_s: float, attempt: int, key: str = "") -> float:
    """Exponential backoff with deterministic jitter for retry attempt
    `attempt` (0-based): `base * 2^attempt * U`, U in [0.5, 1.5) hashed
    from (key, attempt) — reproducible schedules under a fixed key, no
    thundering herd across readahead slots (each slot keys by unit)."""
    jitter = 0.5 + random.Random(f"{key}:{attempt}").random()
    return base_s * (2.0 ** attempt) * jitter


def retry_call(
    fn: Callable[[], Any],
    *,
    attempts: int,
    base_s: float,
    key: str = "",
    retryable: Tuple[type, ...] = (OSError,),
) -> Tuple[Any, int, bool]:
    """Call `fn` with up to `attempts` retries and exponential backoff.

    A `None` return counts as a transient failure too (the native
    reader's short-read signal). Returns `(result, retries_used,
    recovered)`; exhaustion returns `(None, attempts, False)` — the
    caller degrades (pyarrow fallback), it never re-raises. Exceptions
    outside `retryable` propagate untouched."""
    retries = 0
    for attempt in range(attempts + 1):
        try:
            result = fn()
        except retryable:
            result = None
        if result is not None:
            return result, retries, retries > 0
        if attempt < attempts:
            retries += 1
            time.sleep(backoff_s(base_s, attempt, key))
    return None, retries, False


__all__ = [
    "DQ_CANCELLED",
    "DQ_DEADLINE",
    "DQ_DRAIN",
    "DQ_PREEMPTED",
    "DQ_QUOTA",
    "DQ_RETRIES_EXHAUSTED",
    "DQ_STALLED",
    "SOFT_REASONS",
    "RunCancelled",
    "RunController",
    "SharedCancelToken",
    "StallWatchdog",
    "backoff_s",
    "retry_call",
]
