"""Runs the repo's own lint (tools/lint.py) as a tier-1 test, so a
hot-loop host sync in ops/fused.py, an unused import, or a bare except
fails the suite — not just `make lint` (ISSUE 2, satellite).

Also unit-tests the checkers themselves against synthetic sources.
"""

from __future__ import annotations

import importlib.util
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lint_module():
    spec = importlib.util.spec_from_file_location(
        "repo_lint", os.path.join(REPO, "tools", "lint.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_repo_is_lint_clean(capsys):
    lint = _lint_module()
    rc = lint.main()
    out = capsys.readouterr().out
    assert rc == 0, f"repo lint found problems:\n{out}"


def _tmp_source(code: str) -> str:
    fd, path = tempfile.mkstemp(suffix=".py")
    with os.fdopen(fd, "w") as f:
        f.write(code)
    return path


def test_hot_loop_checker_flags_device_get_in_loop():
    lint = _lint_module()
    path = _tmp_source(
        "import jax\n"
        "def f(batches):\n"
        "    out = []\n"
        "    for b in batches:\n"
        "        out.append(jax.device_get(b))\n"
        "    return out\n"
    )
    try:
        findings = lint.check_hot_loops(path)
        assert len(findings) == 1
        assert "device_get" in findings[0]
    finally:
        os.unlink(path)


def test_hot_loop_checker_flags_block_until_ready():
    lint = _lint_module()
    path = _tmp_source(
        "def f(xs):\n"
        "    while xs:\n"
        "        xs.pop().block_until_ready()\n"
    )
    try:
        findings = lint.check_hot_loops(path)
        assert len(findings) == 1
        assert "block_until_ready" in findings[0]
    finally:
        os.unlink(path)


def test_hot_loop_checker_allows_calls_outside_loops():
    lint = _lint_module()
    path = _tmp_source(
        "import jax\n"
        "def f(out):\n"
        "    return jax.device_get(out)\n"
    )
    try:
        assert lint.check_hot_loops(path) == []
    finally:
        os.unlink(path)


def test_timing_checker_flags_clock_reads():
    lint = _lint_module()
    path = _tmp_source(
        "import time\n"
        "from time import monotonic as mono\n"
        "def f():\n"
        "    t0 = time.perf_counter()\n"
        "    t1 = mono()\n"
        "    return time.perf_counter_ns() - t0 + t1\n"
    )
    try:
        findings = lint.check_timing_calls(path)
        assert len(findings) == 3
        assert any("time.perf_counter" in f for f in findings)
        assert any("mono" in f for f in findings)
    finally:
        os.unlink(path)


def test_timing_checker_allows_wall_clock_and_observe():
    lint = _lint_module()
    path = _tmp_source(
        "import time\n"
        "from deequ_tpu.observe.spans import timed_call\n"
        "def f(fn):\n"
        "    ts = time.time()  # wall-clock timestamps (TTL caches) ok\n"
        "    out, dt = timed_call(fn)\n"
        "    return ts, out, dt\n"
    )
    try:
        assert lint.check_timing_calls(path) == []
    finally:
        os.unlink(path)


def test_timing_rule_scopes_to_engine_dirs():
    """The ban covers deequ_tpu/runners + deequ_tpu/ops only — observe/
    (the timing implementation itself) and the benchmark stay free to
    read clocks directly."""
    lint = _lint_module()
    sep = os.sep
    covered = f"deequ_tpu{sep}ops{sep}runtime.py"
    exempt = f"deequ_tpu{sep}observe{sep}spans.py"
    in_scope = lambda rel: any(  # noqa: E731 - mirror of main()'s filter
        rel == d or rel.startswith(d + sep) for d in lint.TIMING_DIRS
    )
    assert in_scope(covered)
    assert not in_scope(exempt)


def test_unused_import_checker():
    lint = _lint_module()
    path = _tmp_source(
        "from __future__ import annotations\n"
        "import os\n"
        "import sys\n"
        "from typing import TYPE_CHECKING, List\n"
        "x: List[int] = []\n"
        "print(sys.argv)\n"
    )
    try:
        findings = lint.check_unused_imports(path)
        # os and TYPE_CHECKING unused; __future__, sys, List used/exempt
        flagged = {f.split("`")[1] for f in findings}
        assert flagged == {"os", "TYPE_CHECKING"}
    finally:
        os.unlink(path)


def test_bare_except_checker():
    lint = _lint_module()
    path = _tmp_source(
        "try:\n    pass\nexcept:\n    pass\n"
        "try:\n    pass\nexcept ValueError:\n    pass\n"
    )
    try:
        findings = lint.check_bare_except(path)
        assert len(findings) == 1
    finally:
        os.unlink(path)


def test_lint_main_is_invocable_as_script():
    import subprocess

    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint.py")],
        capture_output=True,
        text=True,
        timeout=120,
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


# -- PIPELINE: host syncs in stage-worker files (ISSUE 5 satellite) ----------


def test_pipeline_checker_flags_sync_anywhere():
    """Unlike HOTLOOP, the PIPELINE rule bans syncs even OUTSIDE loops:
    all of a stage-worker file runs on (or schedules onto) stage
    threads, where one sync serializes the overlap."""
    lint = _lint_module()
    path = _tmp_source(
        "import jax\n"
        "def prep(batch):\n"
        "    return jax.device_get(batch)\n"
        "def wait(x):\n"
        "    x.block_until_ready()\n"
    )
    try:
        findings = lint.check_pipeline_syncs(path)
    finally:
        os.unlink(path)
    assert len(findings) == 2
    assert all("PIPELINE" in f for f in findings)
    assert any("device_get" in f for f in findings)
    assert any("block_until_ready" in f for f in findings)


def test_pipeline_checker_allows_async_stage_code():
    lint = _lint_module()
    path = _tmp_source(
        "import queue\n"
        "def worker(q, fn, items):\n"
        "    for item in items:\n"
        "        q.put(fn(item))\n"
    )
    try:
        findings = lint.check_pipeline_syncs(path)
    finally:
        os.unlink(path)
    assert findings == []


def test_pipeline_rule_covers_stage_worker_files():
    """The rule is wired to the actual stage-worker files, and those
    files exist — a rename must update the lint scope with it."""
    lint = _lint_module()
    rels = set(lint.PIPELINE_FILES)
    assert os.path.join("deequ_tpu", "ops", "pipeline.py") in rels
    assert os.path.join("deequ_tpu", "data", "source.py") in rels
    for rel in rels:
        assert os.path.exists(os.path.join(REPO, rel)), rel


# -- GLOBALMUT: unguarded module-global mutation (ISSUE 4 satellite) ---------


def test_globalmut_flags_unguarded_cache_write():
    lint = _lint_module()
    path = _tmp_source(
        "_CACHE = {}\n"
        "def get(key):\n"
        "    if key not in _CACHE:\n"
        "        _CACHE[key] = object()\n"
        "    return _CACHE[key]\n"
    )
    try:
        findings = lint.check_global_mutation(path)
    finally:
        os.unlink(path)
    assert any("GLOBALMUT" in f and "_CACHE" in f for f in findings)


def test_globalmut_flags_mutator_method_calls():
    lint = _lint_module()
    path = _tmp_source(
        "_SEEN = []\n"
        "_IDX = {}\n"
        "def add(x):\n"
        "    _SEEN.append(x)\n"
        "def index(k, v):\n"
        "    _IDX.setdefault(k, v)\n"
    )
    try:
        findings = lint.check_global_mutation(path)
    finally:
        os.unlink(path)
    assert sum("GLOBALMUT" in f for f in findings) == 2


def test_globalmut_allows_lock_guarded_mutation():
    lint = _lint_module()
    path = _tmp_source(
        "import threading\n"
        "_CACHE = {}\n"
        "_CACHE_LOCK = threading.Lock()\n"
        "def get(key, value):\n"
        "    with _CACHE_LOCK:\n"
        "        _CACHE[key] = value\n"
        "    return _CACHE[key]\n"
    )
    try:
        findings = lint.check_global_mutation(path)
    finally:
        os.unlink(path)
    assert findings == []


def test_globalmut_allows_allowlisted_assignment():
    lint = _lint_module()
    path = _tmp_source(
        "_REGISTRY = {}  # global-ok: populated once at import time\n"
        "def register(name, fn):\n"
        "    _REGISTRY[name] = fn\n"
    )
    try:
        findings = lint.check_global_mutation(path)
    finally:
        os.unlink(path)
    assert findings == []


def test_globalmut_respects_local_shadowing_and_global_decl():
    lint = _lint_module()
    path = _tmp_source(
        "_STATE = {}\n"
        "def shadowed():\n"
        "    _STATE = {}\n"
        "    _STATE['k'] = 1\n"  # local: fine
        "    return _STATE\n"
        "def declared():\n"
        "    global _STATE\n"
        "    _STATE = {}\n"  # rebind only, not a mutation finding
        "    _STATE['k'] = 1\n"  # mutation of the module global
        "    return _STATE\n"
    )
    try:
        findings = lint.check_global_mutation(path)
    finally:
        os.unlink(path)
    assert sum("GLOBALMUT" in f for f in findings) == 1
    assert all("declared" not in f or "'k'" not in f for f in findings)


# -- OBSPRINT: print() in observability code (ISSUE 6 satellite) -------------


def test_obsprint_checker_flags_print():
    lint = _lint_module()
    path = _tmp_source(
        "def emit(snapshot):\n"
        "    print(snapshot)\n"
    )
    try:
        findings = lint.check_observe_prints(path)
    finally:
        os.unlink(path)
    assert len(findings) == 1
    assert "OBSPRINT" in findings[0]


def test_obsprint_allows_stderr_write():
    lint = _lint_module()
    path = _tmp_source(
        "import sys\n"
        "def emit(line):\n"
        "    sys.stderr.write(line)\n"
    )
    try:
        findings = lint.check_observe_prints(path)
    finally:
        os.unlink(path)
    assert findings == []


def test_obsprint_rule_scopes_to_observe_dir():
    """The ban covers deequ_tpu/observe only — results code elsewhere
    may still print to stdout deliberately."""
    lint = _lint_module()
    sep = os.sep
    covered = f"deequ_tpu{sep}observe{sep}heartbeat.py"
    exempt = f"deequ_tpu{sep}runners{sep}analysis_runner.py"
    in_scope = lambda rel: any(  # noqa: E731 - mirror of main()'s filter
        rel == d or rel.startswith(d + sep) for d in lint.OBSPRINT_DIRS
    )
    assert in_scope(covered)
    assert not in_scope(exempt)


# -- PUSHDOWN: purity of the stats interpreter (ISSUE 7 satellite) -----------


def test_pushdown_checker_flags_pyarrow_import_even_lazy():
    lint = _lint_module()
    path = _tmp_source(
        "def read_stats(path):\n"
        "    import pyarrow.parquet as pq\n"
        "    return pq.ParquetFile(path)\n"
    )
    try:
        findings = lint.check_pushdown_purity(path)
    finally:
        os.unlink(path)
    assert len(findings) == 1
    assert "PUSHDOWN" in findings[0] and "pyarrow" in findings[0]


def test_pushdown_checker_flags_open_call():
    lint = _lint_module()
    path = _tmp_source(
        "def sniff(path):\n"
        "    with open(path, 'rb') as f:\n"
        "        return f.read(4)\n"
    )
    try:
        findings = lint.check_pushdown_purity(path)
    finally:
        os.unlink(path)
    assert len(findings) == 1
    assert "PUSHDOWN" in findings[0] and "open" in findings[0]


def test_pushdown_checker_allows_pure_interpreter_code():
    lint = _lint_module()
    path = _tmp_source(
        "import math\n"
        "from deequ_tpu.lint.interval import Interval\n"
        "def verdict(lo, hi):\n"
        "    return Interval.closed(lo, hi).is_empty or math.isnan(lo)\n"
    )
    try:
        findings = lint.check_pushdown_purity(path)
    finally:
        os.unlink(path)
    assert findings == []


def test_pushdown_rule_covers_the_interpreter_file():
    lint = _lint_module()
    sep = os.sep
    assert f"deequ_tpu{sep}lint{sep}pushdown.py" in lint.PUSHDOWN_FILES


# -- SUBSUME: purity of the plan-subsumption prover (ISSUE 17 satellite) -----


def test_subsume_checker_flags_jax_import_even_lazy():
    lint = _lint_module()
    path = _tmp_source(
        "def fold(xs):\n"
        "    import jax.numpy as jnp\n"
        "    return jnp.sum(jnp.asarray(xs))\n"
    )
    try:
        findings = lint.check_subsume_purity(path)
    finally:
        os.unlink(path)
    assert len(findings) == 1
    assert "SUBSUME" in findings[0] and "jax" in findings[0]


def test_subsume_checker_flags_service_and_relative_runtime_imports():
    lint = _lint_module()
    path = _tmp_source(
        "from deequ_tpu.service.sharing import plan_share_group\n"
        "def peek():\n"
        "    from ..ops import runtime\n"
        "    return runtime\n"
    )
    try:
        findings = lint.check_subsume_purity(path)
    finally:
        os.unlink(path)
    assert len(findings) == 2
    assert all("SUBSUME" in f for f in findings)
    assert any("deequ_tpu.service" in f for f in findings)
    assert any("deequ_tpu.ops" in f for f in findings)


def test_subsume_checker_flags_open_call():
    lint = _lint_module()
    path = _tmp_source(
        "def sniff(path):\n"
        "    with open(path) as f:\n"
        "        return f.read()\n"
    )
    try:
        findings = lint.check_subsume_purity(path)
    finally:
        os.unlink(path)
    assert len(findings) == 1
    assert "SUBSUME" in findings[0] and "open" in findings[0]


def test_subsume_checker_allows_the_pure_prover_imports():
    lint = _lint_module()
    path = _tmp_source(
        "from deequ_tpu.data.expr import parse\n"
        "from deequ_tpu.lint.fold import satisfiability\n"
        "from deequ_tpu.lint.schema import SchemaInfo\n"
        "def implies(a, b, schema):\n"
        "    return satisfiability(parse(a), schema)\n"
    )
    try:
        findings = lint.check_subsume_purity(path)
    finally:
        os.unlink(path)
    assert findings == []


def test_subsume_rule_covers_the_prover_file_and_it_is_clean():
    lint = _lint_module()
    sep = os.sep
    rel = f"deequ_tpu{sep}lint{sep}subsume.py"
    assert rel in lint.SUBSUME_FILES
    path = os.path.join(lint.REPO, rel)
    assert lint.check_subsume_purity(path) == []


def test_globalmut_reads_are_not_findings():
    lint = _lint_module()
    path = _tmp_source(
        "_TABLE = {'a': 1}\n"
        "def read(k):\n"
        "    return _TABLE.get(k, 0) + len(_TABLE)\n"
    )
    try:
        findings = lint.check_global_mutation(path)
    finally:
        os.unlink(path)
    assert findings == []


def test_decode_checker_flags_to_numpy_outside_fallback():
    lint = _lint_module()
    path = _tmp_source(
        "def decode_fast_column(arr):\n"
        "    return arr.to_numpy(zero_copy_only=False)\n"
    )
    try:
        findings = lint.check_decode_copies(path)
    finally:
        os.unlink(path)
    assert len(findings) == 1
    assert "DECODE" in findings[0] and "to_numpy" in findings[0]


def test_decode_checker_flags_frombuffer_copy_idiom():
    lint = _lint_module()
    path = _tmp_source(
        "import numpy as np\n"
        "def decode_fast_column(buf):\n"
        "    return np.frombuffer(buf, dtype=np.int64)\n"
    )
    try:
        findings = lint.check_decode_copies(path)
    finally:
        os.unlink(path)
    assert len(findings) == 1
    assert "frombuffer" in findings[0]


def test_decode_checker_allows_designated_fallback_functions():
    lint = _lint_module()
    path = _tmp_source(
        "import numpy as np\n"
        "def dictionary_uniques_fallback(dictionary):\n"
        "    return dictionary.to_numpy(zero_copy_only=False)\n"
        "def column_fallback(arr):\n"
        "    def inner(b):\n"
        "        return np.frombuffer(b, dtype=np.uint8)\n"
        "    return inner(arr)\n"
    )
    try:
        findings = lint.check_decode_copies(path)
    finally:
        os.unlink(path)
    assert findings == []


def test_decode_checker_allows_buffer_level_code():
    lint = _lint_module()
    path = _tmp_source(
        "import numpy as np\n"
        "def decode(ch, native, out_vals, out_valid):\n"
        "    bufs = ch.buffers()\n"
        "    return native.decode_primitive(\n"
        "        'double', bufs[1].address, None, ch.offset, len(ch),\n"
        "        out_vals, out_valid)\n"
    )
    try:
        findings = lint.check_decode_copies(path)
    finally:
        os.unlink(path)
    assert findings == []


def test_decode_rule_covers_the_fastpath_modules():
    lint = _lint_module()
    sep = os.sep
    assert f"deequ_tpu{sep}data{sep}arrow_decode.py" in lint.DECODE_FILES
    assert f"deequ_tpu{sep}ops{sep}native{sep}__init__.py" in lint.DECODE_FILES


# -- READER: no pyarrow on the native-reader path (ISSUE 11 satellite) --------


def test_reader_checker_flags_pyarrow_import_even_lazy():
    lint = _lint_module()
    path = _tmp_source(
        "def fetch_chunk(fd, meta):\n"
        "    import pyarrow.parquet as pq\n"
        "    return pq.ParquetFile(meta.path)\n"
    )
    try:
        findings = lint.check_reader_purity(path)
    finally:
        os.unlink(path)
    assert len(findings) == 1
    assert "READER" in findings[0] and "pyarrow" in findings[0]


def test_reader_checker_flags_top_level_pyarrow_import():
    lint = _lint_module()
    path = _tmp_source(
        "import pyarrow as pa\n"
        "def decode(raw):\n"
        "    return pa.py_buffer(raw)\n"
    )
    try:
        findings = lint.check_reader_purity(path)
    finally:
        os.unlink(path)
    assert len(findings) == 1


def test_reader_checker_allows_designated_fallback_functions():
    lint = _lint_module()
    path = _tmp_source(
        "def _assemble_column_numpy_fallback(segments):\n"
        "    import pyarrow as pa\n"
        "    return pa.nulls(0)\n"
    )
    try:
        findings = lint.check_reader_purity(path)
    finally:
        os.unlink(path)
    assert findings == []


def test_reader_checker_allows_native_path_code():
    lint = _lint_module()
    path = _tmp_source(
        "import os\n"
        "import numpy as np\n"
        "from deequ_tpu.ops import native\n"
        "def fetch_chunk(fd, meta):\n"
        "    return os.pread(fd, meta.nbytes, meta.offset)\n"
    )
    try:
        findings = lint.check_reader_purity(path)
    finally:
        os.unlink(path)
    assert findings == []


def test_reader_rule_covers_the_dispatch_module():
    lint = _lint_module()
    sep = os.sep
    rels = set(lint.READER_FILES)
    assert f"deequ_tpu{sep}data{sep}native_reader.py" in rels
    assert f"deequ_tpu{sep}data{sep}encfold.py" in rels
    for rel in rels:
        assert os.path.exists(os.path.join(REPO, rel)), rel
    # and the encoded-fold module must actually be clean today: it owns
    # the (run, code) streams end to end, so pyarrow never appears
    assert lint.check_reader_purity(
        os.path.join(REPO, "deequ_tpu", "data", "encfold.py")
    ) == []


# -- FORENSICS: no row samples on telemetry surfaces -------------------------


def test_forensics_checker_flags_module_import():
    lint = _lint_module()
    path = _tmp_source(
        "def snapshot():\n"
        "    from deequ_tpu.observe.forensics import ForensicsReport\n"
        "    return ForensicsReport\n"
    )
    try:
        findings = lint.check_forensics_leak(path)
    finally:
        os.unlink(path)
    assert findings
    assert any("FORENSICS" in f for f in findings)


def test_forensics_checker_flags_plain_import():
    lint = _lint_module()
    path = _tmp_source(
        "import deequ_tpu.observe.forensics as fo\n"
        "def record():\n"
        "    return fo\n"
    )
    try:
        findings = lint.check_forensics_leak(path)
    finally:
        os.unlink(path)
    assert len(findings) == 1
    assert "FORENSICS" in findings[0]


def test_forensics_checker_flags_sample_identifiers():
    lint = _lint_module()
    path = _tmp_source(
        "def emit(report):\n"
        "    # even without the import, touching the sample types leaks\n"
        "    return [s.values for s in report.constraints[0].samples\n"
        "            if isinstance(s, ViolationSample)]\n"
    )
    try:
        findings = lint.check_forensics_leak(path)
    finally:
        os.unlink(path)
    assert len(findings) == 1
    assert "ViolationSample" in findings[0]


def test_forensics_checker_allows_ordinary_telemetry_code():
    lint = _lint_module()
    path = _tmp_source(
        "import json\n"
        "def engine_metric_record(name, value):\n"
        "    return json.dumps({'series': f'engine.{name}', 'value': value})\n"
    )
    try:
        findings = lint.check_forensics_leak(path)
    finally:
        os.unlink(path)
    assert findings == []


def test_forensics_rule_covers_the_telemetry_surfaces():
    lint = _lint_module()
    sep = os.sep
    rels = set(lint.FORENSICS_FILES)
    assert f"deequ_tpu{sep}observe{sep}telemetry.py" in rels
    assert f"deequ_tpu{sep}observe{sep}heartbeat.py" in rels
    assert f"deequ_tpu{sep}repository{sep}engine.py" in rels
    for rel in rels:
        assert os.path.exists(os.path.join(REPO, rel)), rel


def test_serde_rule_covers_the_audit_envelope():
    lint = _lint_module()
    sep = os.sep
    assert f"deequ_tpu{sep}repository{sep}audit.py" in set(lint.SERDE_FILES)


# -- FAULTS: no swallowed exceptions on fault-containment paths ---------------


def test_faults_checker_flags_bare_except():
    lint = _lint_module()
    path = _tmp_source(
        "def worker(q):\n"
        "    try:\n"
        "        q.get_nowait()\n"
        "    except:\n"
        "        return None\n"
    )
    try:
        findings = lint.check_fault_containment(path)
    finally:
        os.unlink(path)
    assert len(findings) == 1
    assert "bare `except:`" in findings[0]


def test_faults_checker_flags_swallowed_exception():
    lint = _lint_module()
    path = _tmp_source(
        "def fetch_unit(fd, meta):\n"
        "    try:\n"
        "        return read(fd, meta)\n"
        "    except OSError:\n"
        "        pass\n"
    )
    try:
        findings = lint.check_fault_containment(path)
    finally:
        os.unlink(path)
    assert len(findings) == 1
    assert "silently swallowed" in findings[0]


def test_faults_checker_allows_fallback_functions_and_fault_ok():
    lint = _lint_module()
    path = _tmp_source(
        "def _close_all_fallback(fds):\n"
        "    for fd in fds:\n"
        "        try:\n"
        "            close(fd)\n"
        "        except OSError:\n"
        "            pass\n"
        "def drain(q):\n"
        "    try:\n"
        "        while True:\n"
        "            q.get_nowait()\n"
        "    except Empty:  # fault-ok: drained\n"
        "        pass\n"
    )
    try:
        findings = lint.check_fault_containment(path)
    finally:
        os.unlink(path)
    assert findings == []


def test_faults_checker_allows_counted_handlers():
    lint = _lint_module()
    path = _tmp_source(
        "def worker(item):\n"
        "    try:\n"
        "        return fn(item)\n"
        "    except Exception:\n"
        "        runtime.record_fault(injected=1)\n"
        "        return fn(item)\n"
    )
    try:
        findings = lint.check_fault_containment(path)
    finally:
        os.unlink(path)
    assert findings == []


def test_faults_registry_parses_harness_points():
    lint = _lint_module()
    registered = lint._registered_fault_points()
    assert registered is not None
    # the harness's public registry and the lint's AST view must agree
    from deequ_tpu.testing import faults

    assert registered == set(faults.FAULT_KINDS)


def test_faults_registration_flags_unknown_point():
    lint = _lint_module()
    registered = lint._registered_fault_points()
    path = _tmp_source(
        "from deequ_tpu.testing import faults\n"
        "def step():\n"
        "    faults.fault_point('read.pread')\n"
        "    faults.fault_point('no.such.point')\n"
    )
    try:
        findings = lint.check_fault_registration(path, registered)
    finally:
        os.unlink(path)
    assert len(findings) == 1
    assert "no.such.point" in findings[0]


def test_faults_rule_covers_stage_worker_and_readahead_files():
    lint = _lint_module()
    sep = os.sep
    rels = set(lint.FAULTS_FILES)
    assert f"deequ_tpu{sep}ops{sep}pipeline.py" in rels
    assert f"deequ_tpu{sep}data{sep}source.py" in rels
    assert f"deequ_tpu{sep}data{sep}native_reader.py" in rels
    assert f"deequ_tpu{sep}data{sep}encfold.py" in rels
    registered = lint._registered_fault_points()
    assert "decode.runs" in registered
    for rel in rels:
        assert os.path.exists(os.path.join(REPO, rel)), rel


def test_faults_rule_covers_service_files():
    """The DQ service files carry multi-tenant blast radius: the
    containment rule must audit them, and the chaos registry must carry
    the service.* points their fault_point() literals name."""
    lint = _lint_module()
    sep = os.sep
    rels = set(lint.FAULTS_FILES)
    assert f"deequ_tpu{sep}service{sep}service.py" in rels
    assert f"deequ_tpu{sep}service{sep}admission.py" in rels
    assert f"deequ_tpu{sep}service{sep}breaker.py" in rels

    registered = lint._registered_fault_points()
    for point in (
        "service.worker",
        "service.scheduler",
        "service.admission",
        "service.queue",
    ):
        assert point in registered, point

    # and the audited files must actually be clean today
    for rel in rels:
        path = os.path.join(REPO, rel)
        assert lint.check_fault_containment(path) == [], rel
        assert lint.check_fault_registration(path, registered) == [], rel


# -- WINDOWS: purity of the windowed state algebra + drift math ---------------


def test_windows_checker_flags_jax_import_even_lazy():
    lint = _lint_module()
    path = _tmp_source(
        "def merge(entries):\n"
        "    import jax.numpy as jnp\n"
        "    return jnp.sum(jnp.asarray(entries))\n"
    )
    try:
        findings = lint.check_windows_purity(path)
    finally:
        os.unlink(path)
    assert len(findings) == 1
    assert "WINDOWS" in findings[0] and "jax" in findings[0]


def test_windows_checker_flags_pyarrow_and_ops_imports():
    lint = _lint_module()
    path = _tmp_source(
        "import pyarrow.parquet as pq\n"
        "def peek():\n"
        "    from deequ_tpu.ops import runtime\n"
        "    return runtime\n"
    )
    try:
        findings = lint.check_windows_purity(path)
    finally:
        os.unlink(path)
    assert len(findings) == 2
    assert all("WINDOWS" in f for f in findings)
    assert any("pyarrow" in f for f in findings)
    assert any("deequ_tpu.ops" in f for f in findings)


def test_windows_checker_flags_open_call():
    lint = _lint_module()
    path = _tmp_source(
        "def load(path):\n"
        "    with open(path, 'rb') as f:\n"
        "        return f.read()\n"
    )
    try:
        findings = lint.check_windows_purity(path)
    finally:
        os.unlink(path)
    assert len(findings) == 1
    assert "WINDOWS" in findings[0] and "open" in findings[0]


def test_windows_checker_allows_numpy_and_state_imports():
    lint = _lint_module()
    path = _tmp_source(
        "import numpy as np\n"
        "from deequ_tpu.repository.states import decode_states\n"
        "from deequ_tpu.testing import faults\n"
        "def fold(blobs, analyzers):\n"
        "    return [decode_states(b, analyzers) for b in blobs]\n"
    )
    try:
        findings = lint.check_windows_purity(path)
    finally:
        os.unlink(path)
    assert findings == []


def test_windows_rule_covers_the_subsystem_and_it_is_clean():
    lint = _lint_module()
    sep = os.sep
    assert f"deequ_tpu{sep}analyzers{sep}drift.py" in lint.WINDOWS_EXTRA_FILES
    windows_dir = os.path.join(lint.REPO, lint.WINDOWS_DIR)
    files = [
        os.path.join(windows_dir, f)
        for f in os.listdir(windows_dir)
        if f.endswith(".py")
    ]
    assert files, "windows/ package has no modules?"
    for path in files + [
        os.path.join(lint.REPO, rel) for rel in lint.WINDOWS_EXTRA_FILES
    ]:
        assert lint.check_windows_purity(path) == [], path
