#!/usr/bin/env python3
"""Repo lint: ruff (when installed) plus pure-AST checks that need no
third-party tooling (ISSUE 2, satellite).

Checks:
  HOTLOOP  — no `jax.device_get(...)` / `.block_until_ready()` calls
             inside for/while loops in deequ_tpu/ops/fused.py: a host
             sync per iteration destroys the double-buffered pipeline
             (each one is a full device drain).
  TIMING   — no direct `time.perf_counter()` / `time.monotonic()` (or
             their `_ns` variants) in deequ_tpu/runners/ and
             deequ_tpu/ops/: engine timing must flow through
             deequ_tpu.observe (span()/timed_call()) so traces stay the
             single source of runtime truth and the disabled path keeps
             its measured near-zero overhead.
  PIPELINE — no `jax.device_get(...)` / `.block_until_ready()` anywhere
             in the stream-pipeline stage-worker files
             (deequ_tpu/ops/pipeline.py, deequ_tpu/data/source.py): a
             host sync on a stage thread serializes the very overlap
             the pipeline exists to create — device syncs belong to
             the fold stage (`PipelinedAggFold`) only.
  GLOBALMUT — module-global dicts/lists in deequ_tpu/ops/, runners/,
             and parallel/ must not be mutated inside functions without
             a lock: engine code runs on worker threads (the family
             pool, user threads) and an unguarded shared cache is the
             exact bug class the ExecutionStats fix in PR 3 removed.
             Guard the mutation with `with <...lock...>:` or allowlist
             the ASSIGNMENT line with a `# global-ok: <reason>` comment.
  OBSPRINT — no `print(...)` calls in deequ_tpu/observe/: heartbeat and
             trace output must flow through a sink, callback, or
             explicit stream write (`sys.stderr.write`) — stdout
             belongs to results (benchmark/run.py's result lines) and
             a stray print corrupts any caller parsing it.
  PUSHDOWN — deequ_tpu/lint/pushdown.py must stay a pure interpreter:
             no pyarrow/pandas import (not even lazily inside a
             function) and no `open(...)` call. Statistics reach it as
             plain RowGroupStats records; ParquetSource.row_group_stats
             is the single reader. Purity keeps every verdict unit-
             testable without files and the lint layer importable
             without pyarrow.
  SUBSUME  — deequ_tpu/lint/subsume.py (the plan-subsumption prover)
             must stay import-pure like PUSHDOWN: no jax/numpy/
             pyarrow/pandas import, no deequ_tpu.service/ops/runners/
             repository/parallel/verification import (not even lazily),
             and no `open(...)` call. The prover's verdicts gate which
             tenants share one fleet-wide scan — they must be provable
             from the plans alone, unit-testable without an
             accelerator, and importable by tools that never touch the
             runtime.
  DECODE   — the fast-path decode modules (data/arrow_decode.py,
             ops/native/) must stay buffer-level: no `.to_numpy(...)`
             and no `frombuffer(...)` copy idioms outside designated
             fallback functions (names ending `_fallback`). The fast
             path's whole point is ONE native pass from arrow buffers
             to Column backing; a host-copy idiom silently reintroduces
             the intermediate materialization it exists to remove.
  READER   — the native parquet reader dispatch
             (deequ_tpu/data/native_reader.py) must not import pyarrow
             outside designated fallback functions (names ending
             `_fallback`): the module exists to own the bytes end to
             end — pread → page decode → arrow-layout buffers → the
             decode/wire kernels — and a pyarrow import on the native
             path means the arrow materialization it replaces crept
             back in. Per-column fallbacks live in source.py, which
             already holds the pyarrow reader.
  SERDE    — no `pickle` (import or call) in the state serde paths
             (deequ_tpu/repository/states.py,
             deequ_tpu/repository/audit.py,
             deequ_tpu/analyzers/state_provider.py): persisted analyzer
             states are exact-width binary formats that round-trip
             bit-exactly and decode safely; pickle is neither (arbitrary
             code execution on load, no cross-version byte stability),
             so one import silently voids both the bit-identity and the
             corrupt-falls-back-to-rescan contracts.
  FORENSICS — telemetry surfaces (deequ_tpu/observe/telemetry.py,
             deequ_tpu/observe/heartbeat.py,
             deequ_tpu/repository/engine.py) must not import
             deequ_tpu.observe.forensics or touch its row-sample types
             (ViolationSample, ConstraintForensics, ForensicsReport,
             render_forensics): sampled row VALUES are data, and the
             `engine.*` series, OpenMetrics text, and heartbeat
             snapshots are operational metadata that leaves the trust
             boundary (dashboards, scrapes, log shippers). Row evidence
             belongs to the audit trail an operator explicitly loads.
  FAULTS   — fault containment in the stage-worker, readahead, and
             DQ-service files (deequ_tpu/ops/pipeline.py,
             deequ_tpu/data/source.py, deequ_tpu/data/native_reader.py,
             deequ_tpu/service/service.py, deequ_tpu/service/admission.py,
             deequ_tpu/service/breaker.py): no bare `except:` and no
             silently-swallowed exceptions (a handler whose body is
             only `pass`) — every contained fault must count itself
             (runtime.record_fault / record_retry) or land in a degrade
             path. Designated fallbacks stay exempt: any enclosing
             function whose name ends `_fallback`, or an except line
             annotated `# fault-ok: <reason>`. Additionally, every
             `faults.fault_point("<name>")` literal anywhere in
             deequ_tpu/ must name a point registered in
             deequ_tpu/testing/faults.py FAULT_KINDS — an unregistered
             point can never be exercised by the chaos harness, so the
             code behind it is untestable dead weight.
  F401*    — unused imports (fallback when ruff is unavailable).
  E722*    — bare `except:` (fallback when ruff is unavailable).

Exit code 0 = clean, 1 = findings. Run via `make lint` or directly:
    python tools/lint.py
"""

from __future__ import annotations

import ast
import glob
import os
import shutil
import subprocess
import sys
from typing import Iterator, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HOT_LOOP_FILES = [os.path.join("deequ_tpu", "ops", "fused.py")]
HOT_LOOP_FORBIDDEN = {"device_get", "block_until_ready"}
# Stage-worker files where a host sync is banned OUTRIGHT (not just in
# loops): their code runs on pipeline stage threads, where one sync
# serializes the decode/prep/compute overlap.
PIPELINE_FILES = [
    os.path.join("deequ_tpu", "ops", "pipeline.py"),
    os.path.join("deequ_tpu", "data", "source.py"),
]
PIPELINE_FORBIDDEN = {"device_get", "block_until_ready"}
# Engine dirs where ad-hoc clock reads are banned (observe/ owns timing).
TIMING_DIRS = (
    os.path.join("deequ_tpu", "runners"),
    os.path.join("deequ_tpu", "ops"),
)
TIMING_FORBIDDEN = {
    "perf_counter",
    "perf_counter_ns",
    "monotonic",
    "monotonic_ns",
}
# Dirs where module-global mutable state must be lock-guarded (engine
# code here runs on worker threads: family pool, user threads, mesh).
GLOBALMUT_DIRS = (
    os.path.join("deequ_tpu", "ops"),
    os.path.join("deequ_tpu", "runners"),
    os.path.join("deequ_tpu", "parallel"),
)
# Dirs where `print(` is banned outright: observability output must go
# through a sink/callback/stream-write, never stdout.
OBSPRINT_DIRS = (os.path.join("deequ_tpu", "observe"),)
# Pure-interpreter files: no pyarrow/pandas imports, no open() calls.
PUSHDOWN_FILES = [os.path.join("deequ_tpu", "lint", "pushdown.py")]
PUSHDOWN_FORBIDDEN_MODULES = {"pyarrow", "pandas"}

SUBSUME_FILES = [os.path.join("deequ_tpu", "lint", "subsume.py")]
SUBSUME_FORBIDDEN_MODULES = {"jax", "jaxlib", "numpy", "pyarrow", "pandas"}
SUBSUME_FORBIDDEN_PREFIXES = (
    "deequ_tpu.service",
    "deequ_tpu.ops",
    "deequ_tpu.runners",
    "deequ_tpu.repository",
    "deequ_tpu.parallel",
    "deequ_tpu.verification",
)
# Windowed state algebra + drift math: host-side planning and
# host-side numpy statistics only. No jax/pyarrow/pandas (a window
# query must resolve with zero data rows read and no kernel dispatch),
# and no deequ_tpu.ops imports (sketch behavior is reached through the
# state objects' own methods); numpy IS allowed — the drift statistics
# are host arithmetic. `open(...)` is banned: all persistence goes
# through the StateRepository surface.
WINDOWS_DIR = os.path.join("deequ_tpu", "windows")
WINDOWS_EXTRA_FILES = [os.path.join("deequ_tpu", "analyzers", "drift.py")]
WINDOWS_FORBIDDEN_MODULES = {"jax", "jaxlib", "pyarrow", "pandas"}
WINDOWS_FORBIDDEN_PREFIXES = ("deequ_tpu.ops",)
# Fast-path decode modules: buffer-level only, no host-copy idioms
# outside designated fallback functions (names ending `_fallback`).
DECODE_FILES = [
    os.path.join("deequ_tpu", "data", "arrow_decode.py"),
    os.path.join("deequ_tpu", "ops", "native", "__init__.py"),
]
# Native-reader dispatch: pyarrow must not appear outside designated
# `*_fallback` functions — the module owns the bytes end to end.
READER_FILES = [
    os.path.join("deequ_tpu", "data", "native_reader.py"),
    os.path.join("deequ_tpu", "data", "encfold.py"),
]
READER_FORBIDDEN_MODULES = {"pyarrow"}
# State serde paths: pickle is banned in any form (import, from-import,
# attribute call) — persisted states are versioned exact-width binary.
SERDE_FILES = [
    os.path.join("deequ_tpu", "repository", "states.py"),
    os.path.join("deequ_tpu", "repository", "audit.py"),
    os.path.join("deequ_tpu", "analyzers", "state_provider.py"),
]
# Stage-worker, readahead, and DQ-service files where swallowed
# exceptions are banned: a fault contained here must be counted or
# degrade loudly. The service files carry multi-tenant blast radius —
# a silently-eaten worker fault would fail other tenants' runs with no
# forensics at all.
FAULTS_FILES = [
    os.path.join("deequ_tpu", "ops", "pipeline.py"),
    os.path.join("deequ_tpu", "data", "source.py"),
    os.path.join("deequ_tpu", "data", "native_reader.py"),
    os.path.join("deequ_tpu", "data", "encfold.py"),
    os.path.join("deequ_tpu", "service", "service.py"),
    os.path.join("deequ_tpu", "service", "admission.py"),
    os.path.join("deequ_tpu", "service", "breaker.py"),
    os.path.join("deequ_tpu", "parallel", "shard.py"),
    os.path.join("deequ_tpu", "parallel", "multihost.py"),
]
# The chaos harness's registry: every fault_point("<name>") literal in
# deequ_tpu/ must be a key of FAULT_KINDS in this module.
FAULTS_REGISTRY = os.path.join("deequ_tpu", "testing", "faults.py")
# Telemetry surfaces where forensics row samples are banned: these
# records leave the trust boundary (scrapes, dashboards, log shippers),
# and sampled row values must never ride along.
FORENSICS_FILES = [
    os.path.join("deequ_tpu", "observe", "telemetry.py"),
    os.path.join("deequ_tpu", "observe", "heartbeat.py"),
    os.path.join("deequ_tpu", "repository", "engine.py"),
]
FORENSICS_FORBIDDEN_MODULE = "deequ_tpu.observe.forensics"
FORENSICS_FORBIDDEN_NAMES = {
    "ViolationSample",
    "ConstraintForensics",
    "ForensicsReport",
    "render_forensics",
}
DECODE_FORBIDDEN_ATTRS = {"to_numpy", "frombuffer"}
# Host pack idioms banned inside the decode-to-wire fused path (any
# function or class whose name contains `wire`): the wire kernels emit
# packed bits and shifted/narrowed values directly, so a packbits/astype
# there means the serial numpy pack crept back in. Designated
# `*_fallback` functions stay exempt — they ARE the host re-read.
DECODE_WIRE_FORBIDDEN_ATTRS = {"packbits", "astype"}
GLOBALMUT_MUTATORS = {
    "append",
    "extend",
    "insert",
    "add",
    "update",
    "setdefault",
    "pop",
    "popitem",
    "clear",
    "remove",
    "discard",
}


def _python_files() -> Iterator[str]:
    for top in ("deequ_tpu", "tests", "tools"):
        root = os.path.join(REPO, top)
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for name in sorted(filenames):
                if name.endswith(".py"):
                    yield os.path.join(dirpath, name)


def _rel(path: str) -> str:
    return os.path.relpath(path, REPO)


# -- HOTLOOP: host syncs inside scan-loop bodies ----------------------------


def check_hot_loops(path: str) -> List[str]:
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    findings: List[str] = []

    class Visitor(ast.NodeVisitor):
        def __init__(self) -> None:
            self.loop_depth = 0

        def _loop(self, node: ast.AST) -> None:
            self.loop_depth += 1
            self.generic_visit(node)
            self.loop_depth -= 1

        visit_For = visit_While = visit_AsyncFor = _loop

        def visit_Call(self, node: ast.Call) -> None:
            if self.loop_depth > 0 and isinstance(node.func, ast.Attribute):
                if node.func.attr in HOT_LOOP_FORBIDDEN:
                    findings.append(
                        f"{_rel(path)}:{node.lineno}: HOTLOOP "
                        f"`.{node.func.attr}` inside a loop body — each call "
                        f"is a device drain; hoist it out of the loop"
                    )
            self.generic_visit(node)

    Visitor().visit(tree)
    return findings


# -- PIPELINE: host syncs in stage-worker files ------------------------------


def check_pipeline_syncs(path: str) -> List[str]:
    """Flag `jax.device_get(...)` / `.block_until_ready()` calls anywhere
    in a stage-worker file: stage threads must stay async — the fold
    stage (`PipelinedAggFold` in ops/fused.py) owns every device sync."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    findings: List[str] = []
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in PIPELINE_FORBIDDEN
        ):
            findings.append(
                f"{_rel(path)}:{node.lineno}: PIPELINE "
                f"`.{node.func.attr}` in a stage-worker file — a host "
                f"sync on a stage thread serializes the pipeline; move "
                f"the sync to the fold stage (PipelinedAggFold)"
            )
    return findings


# -- TIMING: ad-hoc clock reads in engine code -------------------------------


def check_timing_calls(path: str) -> List[str]:
    """Flag `time.perf_counter()`/`time.monotonic()` (and `_ns`) calls —
    direct or via `from time import ...` — in engine dirs. Timing there
    belongs to deequ_tpu.observe: `span(...)` for traced regions,
    `timed_call(...)` for one-off measurements."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    # names bound by `from time import perf_counter [as x]`
    local_clocks = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module == "time":
            for alias in node.names:
                if alias.name in TIMING_FORBIDDEN:
                    local_clocks.add(alias.asname or alias.name)
    findings: List[str] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        hit = None
        if (
            isinstance(func, ast.Attribute)
            and func.attr in TIMING_FORBIDDEN
            and isinstance(func.value, ast.Name)
            and func.value.id == "time"
        ):
            hit = f"time.{func.attr}"
        elif isinstance(func, ast.Name) and func.id in local_clocks:
            hit = func.id
        if hit is not None:
            findings.append(
                f"{_rel(path)}:{node.lineno}: TIMING `{hit}()` in engine "
                f"code — use deequ_tpu.observe (span()/timed_call()) so "
                f"the measurement lands in the trace"
            )
    return findings


# -- OBSPRINT: print() in observability code ---------------------------------


def check_observe_prints(path: str) -> List[str]:
    """Flag any `print(...)` call in deequ_tpu/observe/: heartbeat and
    trace announcements must use a registered sink/callback or an
    explicit `sys.stderr.write` — stdout is reserved for results."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    return [
        f"{_rel(path)}:{node.lineno}: OBSPRINT `print(...)` in "
        f"observability code — emit through a sink/callback or "
        f"`sys.stderr.write`; stdout belongs to results"
        for node in ast.walk(tree)
        if isinstance(node, ast.Call)
        and isinstance(node.func, ast.Name)
        and node.func.id == "print"
    ]


# -- PUSHDOWN: purity of the stats interpreter -------------------------------


def check_pushdown_purity(path: str) -> List[str]:
    """Flag pyarrow/pandas imports (top-level or inside any function)
    and `open(...)` calls in the pushdown interpreter: statistics must
    arrive as plain RowGroupStats records through
    ParquetSource.row_group_stats — never read here."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    findings: List[str] = []
    for node in ast.walk(tree):
        modules: List[str] = []
        if isinstance(node, ast.Import):
            modules = [alias.name for alias in node.names]
        elif isinstance(node, ast.ImportFrom) and node.module:
            modules = [node.module]
        for mod in modules:
            if mod.split(".")[0] in PUSHDOWN_FORBIDDEN_MODULES:
                findings.append(
                    f"{_rel(path)}:{node.lineno}: PUSHDOWN `{mod}` import "
                    f"in the stats interpreter — statistics arrive as "
                    f"RowGroupStats records; the only reader is "
                    f"ParquetSource.row_group_stats"
                )
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id == "open"
        ):
            findings.append(
                f"{_rel(path)}:{node.lineno}: PUSHDOWN `open(...)` in the "
                f"stats interpreter — it must never touch files; pass "
                f"RowGroupStats in"
            )
    return findings


# -- SUBSUME: purity of the plan-subsumption prover ---------------------------


def check_subsume_purity(path: str) -> List[str]:
    """Flag accelerator/runtime imports (top-level or inside any
    function) and `open(...)` calls in the subsumption prover: its
    verdicts gate fleet-wide scan sharing and must be provable from
    the plans alone — no jax, no table IO, no service machinery."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    findings: List[str] = []
    for node in ast.walk(tree):
        modules: List[str] = []
        if isinstance(node, ast.Import):
            modules = [alias.name for alias in node.names]
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                # relative import: resolve against the prover's package
                # (deequ_tpu.lint for level 1, deequ_tpu for level 2)
                base = "deequ_tpu.lint" if node.level == 1 else "deequ_tpu"
                modules = [f"{base}.{node.module}" if node.module else base]
            elif node.module:
                modules = [node.module]
        for mod in modules:
            bad = mod.split(".")[0] in SUBSUME_FORBIDDEN_MODULES or any(
                mod == p or mod.startswith(p + ".")
                for p in SUBSUME_FORBIDDEN_PREFIXES
            )
            if bad:
                findings.append(
                    f"{_rel(path)}:{node.lineno}: SUBSUME `{mod}` import "
                    f"in the subsumption prover — containment verdicts "
                    f"must be provable from the plans alone (expression "
                    f"AST + lint lattice only)"
                )
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id == "open"
        ):
            findings.append(
                f"{_rel(path)}:{node.lineno}: SUBSUME `open(...)` in the "
                f"subsumption prover — it must never touch files; plans "
                f"and schemas arrive as arguments"
            )
    return findings


# -- WINDOWS: purity of the windowed state algebra + drift math ---------------


def check_windows_purity(path: str) -> List[str]:
    """Flag accelerator/table-IO imports and `open(...)` calls in the
    windows/ package and the drift statistics: a window query answers
    from persisted states alone (zero rows read, no kernel dispatch),
    and the drift math is host-side numpy — jax, pyarrow, pandas, and
    `deequ_tpu.ops` must never appear on that path."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    findings: List[str] = []
    # relative-import base from the file's own package
    pkg = os.path.dirname(_rel(path)).replace(os.sep, ".")
    for node in ast.walk(tree):
        modules: List[str] = []
        if isinstance(node, ast.Import):
            modules = [alias.name for alias in node.names]
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                parts = pkg.split(".")
                base = ".".join(parts[: len(parts) - node.level + 1])
                modules = [f"{base}.{node.module}" if node.module else base]
            elif node.module:
                modules = [node.module]
        for mod in modules:
            bad = mod.split(".")[0] in WINDOWS_FORBIDDEN_MODULES or any(
                mod == p or mod.startswith(p + ".")
                for p in WINDOWS_FORBIDDEN_PREFIXES
            )
            if bad:
                findings.append(
                    f"{_rel(path)}:{node.lineno}: WINDOWS `{mod}` import "
                    f"on the windowed-query/drift path — windows resolve "
                    f"from persisted states with zero rows read, and "
                    f"drift math is host-side numpy (no jax/pyarrow/"
                    f"pandas, no deequ_tpu.ops)"
                )
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id == "open"
        ):
            findings.append(
                f"{_rel(path)}:{node.lineno}: WINDOWS `open(...)` on the "
                f"windowed-query/drift path — all persistence goes "
                f"through the StateRepository surface"
            )
    return findings


# -- READER: no pyarrow on the native-reader path ------------------------------


def check_reader_purity(path: str) -> List[str]:
    """Flag pyarrow imports in the native-reader dispatch outside
    designated fallback functions (any enclosing function whose name
    ends `_fallback`). The module's contract is page bytes straight to
    arrow-layout buffers through the native kernels; a pyarrow import on
    that path reintroduces the materialization the reader removes."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    findings: List[str] = []

    def walk(node: ast.AST, in_fallback: bool) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            in_fallback = in_fallback or node.name.endswith("_fallback")
        if not in_fallback:
            modules: List[str] = []
            if isinstance(node, ast.Import):
                modules = [alias.name for alias in node.names]
            elif isinstance(node, ast.ImportFrom) and node.module:
                modules = [node.module]
            for mod in modules:
                if mod.split(".")[0] in READER_FORBIDDEN_MODULES:
                    findings.append(
                        f"{_rel(path)}:{node.lineno}: READER `{mod}` import "
                        f"on the native reader path — the reader owns the "
                        f"bytes end to end; arrow fallbacks live in "
                        f"source.py or a designated `*_fallback` function"
                    )
        for child in ast.iter_child_nodes(node):
            walk(child, in_fallback)

    walk(tree, False)
    return findings


# -- SERDE: no pickle in the state serde paths --------------------------------


def check_serde_pickle(path: str) -> List[str]:
    """Flag any appearance of pickle in the state serde paths: plain or
    from-imports (top-level or inside any function, including the
    `cPickle`/`dill`/`cloudpickle` spellings) and `pickle.loads/dumps`
    attribute calls. Persisted analyzer states must stay exact-width
    versioned binary — pickle would execute arbitrary bytecode on load
    and break byte stability across versions."""
    serde_banned = {"pickle", "cPickle", "_pickle", "dill", "cloudpickle"}
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    findings: List[str] = []
    for node in ast.walk(tree):
        modules: List[str] = []
        if isinstance(node, ast.Import):
            modules = [alias.name for alias in node.names]
        elif isinstance(node, ast.ImportFrom) and node.module:
            modules = [node.module]
        for mod in modules:
            if mod.split(".")[0] in serde_banned:
                findings.append(
                    f"{_rel(path)}:{node.lineno}: SERDE `{mod}` import in "
                    f"a state serde path — persisted states are versioned "
                    f"exact-width binary; pickle voids the bit-identity "
                    f"and safe-decode contracts"
                )
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and isinstance(node.func.value, ast.Name)
            and node.func.value.id in serde_banned
        ):
            findings.append(
                f"{_rel(path)}:{node.lineno}: SERDE "
                f"`{node.func.value.id}.{node.func.attr}(...)` call in a "
                f"state serde path — use the versioned binary envelope"
            )
    return findings


# -- FORENSICS: no row samples on telemetry surfaces --------------------------


def check_forensics_leak(path: str) -> List[str]:
    """Flag imports of deequ_tpu.observe.forensics and any use of its
    row-sample identifiers in telemetry-surface files. Telemetry records
    (`engine.*` series, OpenMetrics text, heartbeat snapshots) are
    operational metadata that leaves the trust boundary; sampled row
    VALUES stay in the audit trail an operator explicitly loads."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    findings: List[str] = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name == FORENSICS_FORBIDDEN_MODULE or (
                    alias.name.startswith(FORENSICS_FORBIDDEN_MODULE + ".")
                ):
                    findings.append(
                        f"{_rel(path)}:{node.lineno}: FORENSICS "
                        f"`{alias.name}` import on a telemetry surface — "
                        f"sampled row values must never reach engine.* "
                        f"records, OpenMetrics text, or heartbeat output"
                    )
        elif isinstance(node, ast.ImportFrom) and node.module:
            if node.module == FORENSICS_FORBIDDEN_MODULE or node.module.startswith(
                FORENSICS_FORBIDDEN_MODULE + "."
            ):
                findings.append(
                    f"{_rel(path)}:{node.lineno}: FORENSICS import from "
                    f"`{node.module}` on a telemetry surface — sampled row "
                    f"values must never reach engine.* records, OpenMetrics "
                    f"text, or heartbeat output"
                )
        else:
            name = None
            if isinstance(node, ast.Name):
                name = node.id
            elif isinstance(node, ast.Attribute):
                name = node.attr
            if name in FORENSICS_FORBIDDEN_NAMES:
                findings.append(
                    f"{_rel(path)}:{node.lineno}: FORENSICS `{name}` on a "
                    f"telemetry surface — row-sample types are banned here; "
                    f"row evidence belongs to the audit trail only"
                )
    return findings


# -- DECODE: no host-copy idioms in fast-path decode modules -----------------


def check_decode_copies(path: str) -> List[str]:
    """Flag `.to_numpy(...)` / `.frombuffer(...)` calls in the fast-path
    decode modules outside designated fallback functions (any enclosing
    function whose name ends `_fallback`). The fast path exists to
    replace exactly these per-column host copies with one native pass
    over the arrow buffers; host materialization belongs in the
    designated fallbacks (e.g. table.py's _column_from_arrow_fallback).

    Inside the decode-to-wire fused path (functions/classes named
    `*wire*`) the rule additionally bans the `.packbits(...)` /
    `.astype(...)` pack idioms: the wire kernels already emit packed
    bits and shifted/narrowed values, so those calls mean the serial
    numpy pack crept back in. `*_fallback` functions stay exempt."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    findings: List[str] = []

    def walk(node: ast.AST, in_fallback: bool, in_wire: bool) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            in_fallback = in_fallback or node.name.endswith("_fallback")
            in_wire = in_wire or "wire" in node.name.lower()
        elif isinstance(node, ast.ClassDef):
            in_wire = in_wire or "wire" in node.name.lower()
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and not in_fallback
        ):
            if node.func.attr in DECODE_FORBIDDEN_ATTRS:
                findings.append(
                    f"{_rel(path)}:{node.lineno}: DECODE "
                    f"`.{node.func.attr}(...)` in a fast-path decode module "
                    f"— this is the host copy the fast path removes; decode "
                    f"via the native kernels, or move the copy into a "
                    f"designated `*_fallback` function"
                )
            elif in_wire and node.func.attr in DECODE_WIRE_FORBIDDEN_ATTRS:
                findings.append(
                    f"{_rel(path)}:{node.lineno}: DECODE "
                    f"`.{node.func.attr}(...)` in the decode-to-wire fused "
                    f"path — the wire kernels already pack bits and "
                    f"narrow/shift values; re-packing on the host defeats "
                    f"the fusion. Move the copy into a designated "
                    f"`*_fallback` function"
                )
        for child in ast.iter_child_nodes(node):
            walk(child, in_fallback, in_wire)

    walk(tree, False, False)
    return findings


# -- GLOBALMUT: unguarded module-global mutable state ------------------------


def _is_mutable_literal(node: ast.AST) -> bool:
    if isinstance(node, (ast.Dict, ast.List, ast.DictComp, ast.ListComp)):
        return True
    return (
        isinstance(node, ast.Call)
        and isinstance(node.func, ast.Name)
        and node.func.id in ("dict", "list")
        and not node.args
        and not node.keywords
    )


def _lockish(expr: ast.AST) -> bool:
    """Does a `with` context expression look like a lock acquisition?
    Heuristic: any name/attribute in it contains 'lock' (e.g.
    `_FUSED_CACHE_LOCK`, `self._lock`, `lock.acquire_timeout(...)`)."""
    for node in ast.walk(expr):
        name = None
        if isinstance(node, ast.Name):
            name = node.id
        elif isinstance(node, ast.Attribute):
            name = node.attr
        if name is not None and "lock" in name.lower():
            return True
    return False


def _bound_names(fn: ast.AST) -> set:
    """Names bound in this function's own scope (params + assignment/
    loop/with/except targets), nested scopes excluded."""
    bound = set()
    args = fn.args
    for a in (
        list(args.posonlyargs) + list(args.args) + list(args.kwonlyargs)
    ):
        bound.add(a.arg)
    if args.vararg:
        bound.add(args.vararg.arg)
    if args.kwarg:
        bound.add(args.kwarg.arg)

    def visit(node: ast.AST) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(
                child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
            ):
                bound.add(child.name)  # a nested def/class binds its name
                continue
            if isinstance(child, ast.Lambda):
                continue
            if isinstance(child, ast.Name) and isinstance(
                child.ctx, (ast.Store, ast.Del)
            ):
                bound.add(child.id)
            visit(child)

    visit(fn)
    return bound


def check_global_mutation(path: str) -> List[str]:
    """Flag mutations of module-level dicts/lists inside functions that
    are neither under a lock `with` nor allowlisted (`# global-ok:` on
    the module-level assignment line)."""
    with open(path, encoding="utf-8") as f:
        source = f.read()
    tree = ast.parse(source, filename=path)
    lines = source.splitlines()

    mutable_globals: set = set()
    for node in tree.body:
        if isinstance(node, ast.Assign):
            targets, value = node.targets, node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets, value = [node.target], node.value
        else:
            continue
        if not _is_mutable_literal(value):
            continue
        line = lines[node.lineno - 1] if node.lineno <= len(lines) else ""
        if "# global-ok" in line:
            continue
        for target in targets:
            if isinstance(target, ast.Name) and target.id != "__all__":
                mutable_globals.add(target.id)
    if not mutable_globals:
        return []

    findings: List[str] = []

    def _hit(name: str, lineno: int, what: str) -> None:
        findings.append(
            f"{_rel(path)}:{lineno}: GLOBALMUT {what} mutates module "
            f"global `{name}` without a lock — wrap in `with <lock>:` "
            f"or allowlist the assignment with `# global-ok: <reason>`"
        )

    def _global_subscript(expr: ast.AST, local: set):
        if (
            isinstance(expr, ast.Subscript)
            and isinstance(expr.value, ast.Name)
            and expr.value.id in mutable_globals
            and expr.value.id not in local
        ):
            return expr.value.id
        return None

    def scan_node(node: ast.AST, local: set, lock_depth: int) -> None:
        if lock_depth == 0:
            if isinstance(node, ast.Call) and isinstance(
                node.func, ast.Attribute
            ):
                func = node.func
                if (
                    isinstance(func.value, ast.Name)
                    and func.value.id in mutable_globals
                    and func.value.id not in local
                    and func.attr in GLOBALMUT_MUTATORS
                ):
                    _hit(func.value.id, node.lineno, f"`.{func.attr}()`")
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = (
                    node.targets
                    if isinstance(node, ast.Assign)
                    else [node.target]
                )
                for target in targets:
                    name = _global_subscript(target, local)
                    if name is not None:
                        _hit(name, node.lineno, "subscript assignment")
            elif isinstance(node, ast.Delete):
                for target in node.targets:
                    name = _global_subscript(target, local)
                    if name is not None:
                        _hit(name, node.lineno, "`del` on subscript")
        if isinstance(node, (ast.With, ast.AsyncWith)) and any(
            _lockish(item.context_expr) for item in node.items
        ):
            lock_depth += 1
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scan_function(child, local, lock_depth)
            elif isinstance(child, ast.Lambda):
                continue  # expression-only: cannot contain mutations above
            else:
                scan_node(child, local, lock_depth)

    def scan_function(fn: ast.AST, outer_local: set, lock_depth: int) -> None:
        declared_global = {
            name
            for stmt in ast.walk(fn)
            if isinstance(stmt, ast.Global)
            for name in stmt.names
        }
        local = (outer_local | _bound_names(fn)) - declared_global
        for stmt in fn.body:
            scan_node(stmt, local, lock_depth)

    def scan_class(cls: ast.AST) -> None:
        for sub in cls.body:
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scan_function(sub, set(), 0)
            elif isinstance(sub, ast.ClassDef):
                scan_class(sub)

    for stmt in tree.body:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            scan_function(stmt, set(), 0)
        elif isinstance(stmt, ast.ClassDef):
            scan_class(stmt)
    return findings


# -- F401 fallback: unused imports ------------------------------------------


def _used_names(tree: ast.AST) -> set:
    used = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            used.add(node.id)
        elif isinstance(node, ast.Constant) and isinstance(node.value, str):
            # forward-ref annotations ("Table"), dotted refs, __all__ entries
            for part in node.value.replace(".", " ").replace("[", " ").replace(
                "]", " "
            ).split():
                if part.isidentifier():
                    used.add(part)
    return used


def check_unused_imports(path: str) -> List[str]:
    if os.path.basename(path) == "__init__.py":
        return []  # re-export surface: unused-looking imports are the point
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    used = _used_names(tree)
    findings: List[str] = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                bound = alias.asname or alias.name.split(".")[0]
                if bound not in used:
                    findings.append(
                        f"{_rel(path)}:{node.lineno}: F401 "
                        f"`{alias.name}` imported but unused"
                    )
        elif isinstance(node, ast.ImportFrom):
            if node.module == "__future__":
                continue
            for alias in node.names:
                if alias.name == "*":
                    continue
                bound = alias.asname or alias.name
                if bound not in used:
                    findings.append(
                        f"{_rel(path)}:{node.lineno}: F401 "
                        f"`{alias.name}` imported but unused"
                    )
    return findings


# -- FAULTS: no swallowed exceptions on the fault-containment paths ----------


def check_fault_containment(path: str) -> List[str]:
    """Flag bare `except:` and silently-swallowed exceptions (handlers
    whose body is solely `pass`) in the stage-worker and readahead
    files. A fault contained on these paths must either count itself
    (runtime.record_fault / record_retry) or degrade into a designated
    fallback — a handler that does neither hides the exact class of
    failure the chaos harness exists to exercise. Exempt: any enclosing
    function whose name ends `_fallback`, and except lines annotated
    `# fault-ok: <reason>`."""
    with open(path, encoding="utf-8") as f:
        src = f.read()
    tree = ast.parse(src, filename=path)
    lines = src.splitlines()
    findings: List[str] = []

    def walk(node: ast.AST, in_fallback: bool) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            in_fallback = in_fallback or node.name.endswith("_fallback")
        if isinstance(node, ast.ExceptHandler) and not in_fallback:
            if node.type is None:
                findings.append(
                    f"{_rel(path)}:{node.lineno}: FAULTS bare `except:` on "
                    f"a fault-containment path — name the exception so "
                    f"injected faults stay distinguishable from "
                    f"KeyboardInterrupt/SystemExit"
                )
            elif all(isinstance(stmt, ast.Pass) for stmt in node.body) and (
                "# fault-ok:" not in lines[node.lineno - 1]
            ):
                findings.append(
                    f"{_rel(path)}:{node.lineno}: FAULTS silently swallowed "
                    f"exception — count it (runtime.record_fault / "
                    f"record_retry), degrade via a `*_fallback` function, "
                    f"or annotate the except line `# fault-ok: <reason>`"
                )
        for child in ast.iter_child_nodes(node):
            walk(child, in_fallback)

    walk(tree, False)
    return findings


def _registered_fault_points() -> Optional[set]:
    """FAULT_KINDS keys from the chaos harness, by AST — None when the
    registry module or the dict is missing (reported as a finding)."""
    path = os.path.join(REPO, FAULTS_REGISTRY)
    if not os.path.exists(path):
        return None
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, ast.AnnAssign):
            targets = [node.target]
        else:
            continue
        for target in targets:
            if (
                isinstance(target, ast.Name)
                and target.id == "FAULT_KINDS"
                and isinstance(node.value, ast.Dict)
            ):
                return {
                    key.value
                    for key in node.value.keys
                    if isinstance(key, ast.Constant)
                    and isinstance(key.value, str)
                }
    return None


def check_fault_registration(path: str, registered: set) -> List[str]:
    """Flag `fault_point("<name>")` call literals naming a point absent
    from the harness's FAULT_KINDS registry. An unregistered point can
    never fire under any DEEQU_TPU_FAULTS spec, so the containment code
    behind it is unexercisable by `make chaos` — register the point or
    delete the probe."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    findings: List[str] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        name = func.attr if isinstance(func, ast.Attribute) else (
            func.id if isinstance(func, ast.Name) else None
        )
        if name != "fault_point" or not node.args:
            continue
        arg = node.args[0]
        if not (isinstance(arg, ast.Constant) and isinstance(arg.value, str)):
            continue
        if arg.value not in registered:
            findings.append(
                f"{_rel(path)}:{node.lineno}: FAULTS fault point "
                f"`{arg.value}` is not registered in "
                f"{FAULTS_REGISTRY} FAULT_KINDS — the chaos harness "
                f"can never exercise it"
            )
    return findings


# -- E722 fallback: bare except ---------------------------------------------


def check_bare_except(path: str) -> List[str]:
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    return [
        f"{_rel(path)}:{node.lineno}: E722 bare `except:` — name the exception"
        for node in ast.walk(tree)
        if isinstance(node, ast.ExceptHandler) and node.type is None
    ]


def run_ruff() -> List[str]:
    """Delegate F401/F821/E722 to ruff when it exists (config lives in
    pyproject.toml); None-equivalent empty result plus a sentinel when
    it doesn't."""
    exe = shutil.which("ruff")
    if exe is None:
        return []
    proc = subprocess.run(
        [exe, "check", "deequ_tpu", "tests", "tools"],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=300,
    )
    if proc.returncode == 0:
        return []
    return [line for line in proc.stdout.splitlines() if line.strip()]


def main() -> int:
    findings: List[str] = []

    for rel in HOT_LOOP_FILES:
        path = os.path.join(REPO, rel)
        if os.path.exists(path):
            findings.extend(check_hot_loops(path))

    for rel in PIPELINE_FILES:
        path = os.path.join(REPO, rel)
        if os.path.exists(path):
            findings.extend(check_pipeline_syncs(path))

    for rel in PUSHDOWN_FILES:
        path = os.path.join(REPO, rel)
        if os.path.exists(path):
            findings.extend(check_pushdown_purity(path))

    for rel in SUBSUME_FILES:
        path = os.path.join(REPO, rel)
        if os.path.exists(path):
            findings.extend(check_subsume_purity(path))

    windows_dir = os.path.join(REPO, WINDOWS_DIR)
    windows_paths = (
        sorted(glob.glob(os.path.join(windows_dir, "*.py")))
        if os.path.isdir(windows_dir)
        else []
    ) + [
        os.path.join(REPO, rel)
        for rel in WINDOWS_EXTRA_FILES
        if os.path.exists(os.path.join(REPO, rel))
    ]
    for path in windows_paths:
        findings.extend(check_windows_purity(path))

    for rel in DECODE_FILES:
        path = os.path.join(REPO, rel)
        if os.path.exists(path):
            findings.extend(check_decode_copies(path))

    for rel in READER_FILES:
        path = os.path.join(REPO, rel)
        if os.path.exists(path):
            findings.extend(check_reader_purity(path))

    for rel in SERDE_FILES:
        path = os.path.join(REPO, rel)
        if os.path.exists(path):
            findings.extend(check_serde_pickle(path))

    for rel in FORENSICS_FILES:
        path = os.path.join(REPO, rel)
        if os.path.exists(path):
            findings.extend(check_forensics_leak(path))

    for rel in FAULTS_FILES:
        path = os.path.join(REPO, rel)
        if os.path.exists(path):
            findings.extend(check_fault_containment(path))

    registered = _registered_fault_points()
    if registered is None:
        findings.append(
            f"{FAULTS_REGISTRY}: FAULTS chaos-harness registry "
            f"(FAULT_KINDS dict) not found — fault points cannot be "
            f"validated"
        )

    for path in _python_files():
        rel = _rel(path)
        if any(
            rel == d or rel.startswith(d + os.sep) for d in TIMING_DIRS
        ):
            findings.extend(check_timing_calls(path))
        if any(
            rel == d or rel.startswith(d + os.sep) for d in GLOBALMUT_DIRS
        ):
            findings.extend(check_global_mutation(path))
        if any(
            rel == d or rel.startswith(d + os.sep) for d in OBSPRINT_DIRS
        ):
            findings.extend(check_observe_prints(path))
        if registered is not None and rel.startswith(
            "deequ_tpu" + os.sep
        ):
            findings.extend(check_fault_registration(path, registered))

    if shutil.which("ruff") is not None:
        findings.extend(run_ruff())
    else:
        for path in _python_files():
            findings.extend(check_unused_imports(path))
            findings.extend(check_bare_except(path))

    for line in findings:
        print(line)
    if findings:
        print(f"\n{len(findings)} finding(s)")
        return 1
    print("lint clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
