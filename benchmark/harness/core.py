"""The harness: one cell, one seed, one run.

  set-up   JAX and the chip, the cell's data, the placement probe, the
           warm-up calls (every program compiled or loaded from the
           persistent cache), each printed as a part of `setup_s`
  window   a closed loop of the traffic driver's calls for `--seconds`;
           the call in flight when time runs out is finished
  after    peak device memory; the program's state freed; the comparison
           with the reference; the metrics the cell reports, read by the
           files named after them (end_to_end/<name>.py with --trace 0,
           layers/<name>.py with --trace 1); the result line

Everything that belongs to one cell, configuration, traffic kind or
metric is a file found by name from BENCHMARK.json; this module has no
list of them.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
import traceback
from dataclasses import dataclass, field
from typing import Callable, Dict, List, NamedTuple, Optional

from benchmark.harness.spans import by_category, walk
from benchmark.trace import xplane

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))  # benchmark/
ROOT = os.path.dirname(HERE)  # the checkout
# JAX's persistent compilation cache, where JAX_COMPILATION_CACHE_DIR does
# not name one: a fixed path in the checkout, since the path is part of
# what a later process looks an entry up by
JAX_CACHE = os.path.join(HERE, ".cache", "jax")


class Call(NamedTuple):
    rows: int
    t0: float
    t1: float


@dataclass
class Context:
    bench: dict
    cell: dict  # the cell's BENCHMARK.json entry
    traffic: dict  # the traffic mix: benchmark/traffic/<traffic>.json
    config: dict  # the configuration's file
    seed: int
    seconds: float
    trace: bool
    devices: list
    log: Callable[[str], None] = print


@dataclass
class Run:
    """What the metric readers see of one run."""

    calls: List[Call]
    failed: int
    window: tuple  # (start, end) on time.perf_counter
    setup_s: float
    chips: int
    device_kind: str
    spans: list = field(default_factory=list)  # the window's span forest
    device: Optional[dict] = None  # device id -> trace.xplane.Window
    compiles_in_window: int = 0

    @property
    def rows(self) -> int:
        return sum(c.rows for c in self.calls)


def log(msg: str) -> None:
    print(msg, flush=True)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def process_start() -> float:
    """This process's start on `time.perf_counter`'s clock, from
    /proc (so interpreter start-up counts toward set-up)."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
        return time.perf_counter() - max(age, 0.0)
    except (OSError, ValueError, IndexError):
        return time.perf_counter()


def context(args, devices) -> Context:
    bench = _read(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {c["name"]: c for c in bench["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"no cell {args.workload!r} in BENCHMARK.json")
    cell = cells[args.workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read(os.path.join(ROOT, configs[cell["config"]]["file"]))
    traffic = _read(os.path.join(HERE, "traffic", f"{cell['traffic']}.json"))
    return Context(bench, cell, traffic, config, args.seed, float(args.seconds),
                   bool(args.trace), devices, log)


def use_compile_cache() -> str:
    import jax

    where = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not where:
        where = JAX_CACHE
        jax.config.update("jax_compilation_cache_dir", where)
    # every program, however quick to compile, is written: the run's
    # set-up then loads all of them after the first run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class CompileClock:
    """Programs JAX compiled or loaded, the seconds it took, and how many
    persistent-cache lookups hit, from JAX's monitoring events (copied
    from chip_smoke.py, PR 21)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        self.lookups = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.lookups += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snap(self) -> tuple:
        return self.seconds, self.count, self.lookups, self.hits


def _readers(ctx: Context, section: str, folder: str) -> list:
    out = []
    for m in ctx.bench[section]:
        if "workloads" in m and ctx.cell["name"] not in m["workloads"]:
            continue
        mod = load_module(os.path.join(HERE, folder, f"{m['name']}.py"),
                          f"bench_{folder}_{m['name'].replace('.', '_')}")
        out.append((m, mod))
    return out


def _memory_peak(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() if hasattr(d, "memory_stats") else None
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run(ctx: Context, started: float):
    """One run on `ctx.devices`: (the result line's object, the
    scorecard)."""
    import jax

    from benchmark.reference.reference import Scorecard
    from deequ_tpu.ops import runtime

    clock = CompileClock()
    chips = int(ctx.cell["chips"])
    devices = ctx.devices[:chips]
    parts: Dict[str, float] = {"jax_init": time.perf_counter() - started}
    dev = devices[0]
    ctx.log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
            f"cell {ctx.cell['name']} seed {ctx.seed} seconds {ctx.seconds:g} "
            f"trace {int(ctx.trace)}")
    driver = load_module(
        os.path.join(HERE, "traffic", f"{ctx.traffic['kind']}.py"),
        f"bench_traffic_{ctx.traffic['kind']}",
    ).Driver(ctx)

    t = time.perf_counter()
    driver.prepare()
    parts["data"] = time.perf_counter() - t
    t = time.perf_counter()
    placement = runtime.placement_mode()
    parts["placement"] = time.perf_counter() - t
    ctx.log(f"placement: auto chose {placement}")
    c0 = clock.snap()
    t = time.perf_counter()
    with runtime.monitored() as warm_stats:
        driver.warm()
    parts["warmup"] = time.perf_counter() - t
    c1 = clock.snap()
    ctx.log(
        f"warm-up: {parts['warmup']:.3f}s, of it compile or cache load "
        f"{c1[0] - c0[0]:.3f}s over {c1[1] - c0[1]} programs; persistent cache "
        f"{c1[3]} hits of {c1[2]} lookups in this process; device dispatches "
        f"{warm_stats.device_launches}, kernels {dict(sorted(warm_stats.kernel_traces.items()))}"
    )

    trace_dir = tracer = anchor = None
    stack = contextlib.ExitStack()
    if ctx.trace:
        from deequ_tpu import observe

        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation(xplane.ANCHOR):
            anchor = time.perf_counter()
        tracer = stack.enter_context(observe.tracing())
        window_stats = stack.enter_context(runtime.monitored())
    calls: List[Call] = []
    failed = 0
    w0 = time.perf_counter()
    parts_total = w0 - started
    ctx.log("set-up: " + ", ".join(f"{k} {v:.3f}s" for k, v in parts.items())
            + f"; setup_s {parts_total:.3f}")
    cw = clock.snap()
    with stack:
        while time.perf_counter() - w0 < ctx.seconds:
            try:
                calls.append(driver.step())
            except Exception:  # noqa: BLE001 - a failed call counts, the loop goes on
                failed += 1
                if failed == 1:
                    ctx.log(traceback.format_exc())
    w1 = calls[-1].t1 if calls else time.perf_counter()
    compiles = clock.snap()[1] - cw[1]
    device = None
    if ctx.trace:
        jax.profiler.stop_trace()
        tr = xplane.read(trace_dir)
        if tr.anchor_ns is None:
            raise RuntimeError("the trace has no anchor event")
        to_ns = lambda t: tr.anchor_ns + (t - anchor) * 1e9  # noqa: E731
        device = xplane.reduce(tr, to_ns(w0), to_ns(w1))
        ctx.log(f"window counters: device dispatches {window_stats.device_launches}, "
                f"group passes {window_stats.group_passes}")
        ctx.log("window spans, self seconds by category: " + json.dumps(
            {k: round(v, 6) for k, v in sorted(by_category(tracer.roots).items())}))
    ctx.log(f"window: {len(calls)} calls, {failed} failed, "
            f"{w1 - w0:.3f}s, {compiles} programs compiled or loaded in it")
    memory_peak = _memory_peak(devices)
    driver.release()
    result_run = Run(calls, failed, (w0, w1), parts_total, chips, dev.device_kind,
                     tracer.roots if tracer else [], device, compiles)

    card = Scorecard(ctx.config["guarantees"])
    t = time.perf_counter()
    driver.verify(card)
    card.count("calls_failed", failed, "calls that raised")
    ctx.log(f"reference: compared {card.compared} numbers in "
            f"{time.perf_counter() - t:.3f}s; {len(card.failures)} misses")
    for f in card.failures[:20]:
        ctx.log(f"MISS {f[:300]}")

    metrics = {}
    section, folder = ("per_layer", "layers") if ctx.trace else ("end_to_end", "end_to_end")
    for m, mod in _readers(ctx, section, folder):
        v = mod.read(result_run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    line = {
        "correct": card.ok and bool(calls),
        "attempted": len(calls) + failed,
        "failed": failed,
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": memory_peak},
    }
    if ctx.trace:
        line["device"].update(_busy(device, chips, w1 - w0))
        line["breakdown"] = _breakdown(device, tracer.roots, to_ns, to_ns(w0), to_ns(w1))
        shutil.rmtree(trace_dir, ignore_errors=True)
    line["compared"] = card.as_json()
    return line, card


def _busy(device, chips, window_s) -> dict:
    busy = [device[d].busy_ns / 1e9 for d in device] if device else [0.0]
    return {"busy_s": sum(busy) / max(chips, 1), "window_s": window_s}


def _breakdown(device, roots, to_ns, lo, hi) -> dict:
    """The ten operations that took the most device time, and the ten
    longest idle gaps of the window on the busiest device, each named by
    what the host was doing."""
    if not device:
        return {"device_ops": [], "idle_gaps": []}
    ops: Dict[str, float] = {}
    for w in device.values():
        for name, ns in w.op_ns.items():
            ops[name] = ops.get(name, 0.0) + ns
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    busiest = max(device.values(), key=lambda w: w.busy_ns)
    longest = sorted(xplane.gaps(busiest.busy, lo, hi), key=lambda g: g[0] - g[1])[:10]
    host = [(s.name, to_ns(s.t0), to_ns(s.t1), depth) for s, depth in walk(roots)]
    named = xplane.attribute(longest, host)
    return {
        "device_ops": [[n, ns / 1e9] for n, ns in top],
        "idle_gaps": [[n, ns / 1e9] for n, ns in named],
    }


def main(argv=None) -> int:
    started = process_start()
    parser = argparse.ArgumentParser(description="one run of one benchmark cell")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    use_compile_cache()
    import jax

    devices = jax.devices()
    ctx = context(args, devices)
    chips = int(ctx.cell["chips"])
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"benchmark: cell {ctx.cell['name']} needs {chips} TPU chip(s); "
              f"JAX found {devices}", file=sys.stderr)
        return 3
    line, card = run(ctx, started)
    for text in card.lines():
        print(text, file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0
