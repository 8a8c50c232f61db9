"""One run of one benchmark cell: set-up, the measured window, the check.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up imports the program, builds the cell's graph on the host from
``--seed`` and runs a 1-round warm-up of the cell's own job, which compiles
(or loads from the checkout's compilation cache) every program the window
runs. The window is J user jobs back to back on that graph, each ONE
``Trainer(cfg).run(g)`` call of R rounds with its own seed (``--seed`` + j):
partition and per-job tracing included, each ending when its final global
parameters and accuracy curves are on the host. R and J are the traffic
file's ``rounds`` and ``jobs``, fixed so that the window lasts about
BENCHMARK.json's ``run_seconds`` (R is kept short enough that the
reference's trajectory still separates the control from the program); a
shorter ``--seconds`` (a trial) runs that share of the jobs. Then the
plain reference follows every job from its seed and ``bench.compare``
decides ``correct`` over the jobs.

With ``--trace 0`` the program's telemetry stays off and the line carries
the cell's end-to-end metrics; with ``--trace 1`` the program's spans and
the JAX profiler are on for the window and the line carries the per-layer
metrics (readers in ``bench/metrics/``), ``busy_s``/``window_s`` and a
``breakdown``. The last line of standard output is the result; the last
lines of standard error are the compared numbers beside their limits.
Without a TPU, with fewer chips than the cell asks for, or on a chip kind
missing from ``bench/peaks.json``, it prints no result and exits 2.
"""
from __future__ import annotations

import argparse
import importlib
import json
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

from bench import spec

# Where JAX keeps compiled programs: a fixed directory inside the checkout,
# so that only a cell's first run in a checkout compiles.
CACHE_DIR = spec.ROOT / ".jax_compile_cache"
WARMUP_ROUNDS = 1
EXIT_NO_CHIP = 2


class NoChip(RuntimeError):
    """The machine cannot run this cell: no result is printed."""


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def configure_jax():
    """Compilation cache in the checkout, every program cached."""
    import jax

    CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def check_devices(jax, chips: int):
    """The first ``chips`` TPU devices and their peak row, or NoChip."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"the first JAX device is {devices[0].platform!r}, not a TPU")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devices)}")
    kind = devices[0].device_kind
    try:
        peaks = spec.load_peaks(kind)
    except KeyError as err:
        raise NoChip(str(err)) from None
    return devices[:chips], peaks


class CompileCounter:
    """Backend compiles and compilation-cache loads, from jax.monitoring.

    JAX reports a ``backend_compile_duration`` for every program it gets
    from XLA, loaded from the persistent cache or compiled; the compiles
    are those less the cache hits."""

    BACKEND = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        from jax import monitoring

        self.programs = 0
        self.hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **kw) -> None:
        if event == self.BACKEND:
            self.programs += 1

    def _event(self, event: str, **kw) -> None:
        if event == self.HIT:
            self.hits += 1

    def snapshot(self):
        """(compiles, cache loads) so far."""
        return self.programs - self.hits, self.hits


def build_graph(config: Dict[str, Any], seed: int):
    """The cell's graph from ``seed`` on the host, as the program's type."""
    from bench import graphgen
    from repro.graphs.graph import Graph

    arrays = graphgen.make_sbm(config["graph"], seed)
    return arrays, Graph(**arrays)


def run_job(cell: spec.Cell, g, seed: int, rounds: int):
    from repro.federated import Trainer

    cfg = spec.federated_config(cell.config, cell.traffic, seed=seed, rounds=rounds)
    return Trainer(cfg).run(g)


def window_jobs(cell: spec.Cell, seconds: float) -> int:
    """The traffic's fixed number of jobs, or its share for a shorter trial."""
    jobs = int(cell.traffic["jobs"])
    if seconds >= cell.run_seconds:
        return jobs
    return max(1, int(round(jobs * seconds / cell.run_seconds)))


def peak_memory(devices) -> int:
    """The largest ``peak_bytes_in_use`` that JAX reports over the chips."""
    stats = [d.memory_stats() or {} for d in devices]
    log(f"memory_stats of chip 0: {stats[0]}")
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


class Window:
    """The measured call, with the profiler and the program's spans around
    it when traced."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.marker_perf_ns = 0
        self.spans: List = []
        self.trace = None

    def run(self, fn, keep_trace: Optional[str] = None):
        """``fn()`` and its host-clock bounds; traced, the profile is read
        into ``self.trace`` (and copied to ``keep_trace``) and removed."""
        if not self.traced:
            t0 = time.perf_counter()
            out = fn()
            return out, t0, time.perf_counter()
        with tempfile.TemporaryDirectory(prefix="bench-trace-") as trace_dir:
            out, t0, t1 = self._traced(fn, trace_dir)
            from bench import xplane

            self.trace = xplane.load(xplane.find_xplane(trace_dir))
            if keep_trace:
                shutil.copytree(trace_dir, keep_trace, dirs_exist_ok=True)
        return out, t0, t1

    def _traced(self, fn, trace_dir: str):
        import jax
        from repro import telemetry

        telemetry.reset()
        telemetry.enable()
        jax.profiler.start_trace(trace_dir)
        try:
            self.marker_perf_ns = time.perf_counter_ns()
            with jax.profiler.TraceAnnotation("bench.marker"):
                pass
            with jax.profiler.TraceAnnotation("bench.window"):
                t0 = time.perf_counter()
                out = fn()
                t1 = time.perf_counter()
        finally:
            jax.profiler.stop_trace()
            telemetry.disable()
        self.spans = list(telemetry.tracer.records)
        telemetry.reset()
        return out, t0, t1


class RunView:
    """What a per-layer metric reader sees of one traced run."""

    def __init__(self, *, cell, peaks, chips, rounds, wall_s, trace, spans,
                 marker_perf_ns, graph, results):
        self.cell = cell
        self.peaks = peaks
        self.chips = chips
        self.rounds = rounds
        self.wall_s = wall_s
        self.trace = trace
        self.spans = spans
        self.marker_perf_ns = marker_perf_ns
        self.graph = graph
        self.results = results       # each job's Trainer.run result

    def work(self, name: str):
        return importlib.import_module(f"bench.work.{name}")

    def span_on_trace(self, rec):
        """(start, end) of a host span on the trace clock."""
        s = self.trace.to_trace_ns(rec.start_ns, self.marker_perf_ns)
        return s, s + rec.dur_ns


def read_per_layer(cell: spec.Cell, view: RunView) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in cell.per_layer:
        if not cell.reports(m):
            continue
        reader = importlib.import_module(f"bench.metrics.{m['name']}")
        value = reader.read(view)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def breakdown(view: RunView) -> Dict[str, List]:
    """The ten device ops that took most time (seconds per chip) and the
    device's idle time by the innermost program span open on the host at
    the middle of each idle gap (seconds per chip)."""
    from bench import xplane

    chips = len(view.trace.ops)
    ops: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    spans = sorted((view.span_on_trace(r) + (r.depth, r.name) for r in view.spans))
    for chip_ops in view.trace.ops.values():
        for name, sec in xplane.op_seconds(chip_ops).items():
            ops[name] = ops.get(name, 0.0) + sec / chips
        for s, e in xplane.idle_gaps(chip_ops, view.trace.window):
            mid = (s + e) // 2
            label, depth = "no program span", -1
            for ss, se, d, name in spans:
                if ss > mid:
                    break
                if se > mid and d > depth:
                    label, depth = name, d
            idle[label] = idle.get(label, 0.0) + (e - s) / 1e9 / chips
    top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {"device_ops": top(ops), "idle_gaps": top(idle)}


def check(cell: spec.Cell, arrays, seeds: List[int], rounds: int, results) -> tuple:
    """Every job against the reference from its seed, the numbers taken
    over the jobs by ``bench.compare.over_jobs``."""
    from bench import compare
    from bench.reference import federated

    per_job = []
    for seed, result in zip(seeds, results):
        ref = federated.run(cell.config, cell.job, arrays, seed, rounds)
        per_job.append(compare.numbers(result, ref, rounds))
        log(f"job of seed {seed}: {json.dumps(per_job[-1])}")
    nums = compare.over_jobs(per_job)
    ok, lines = compare.judge(nums, cell.limits["limits"])
    return ok, nums, lines


def execute(cell: spec.Cell, *, seed: int, seconds: float, traced: bool,
            devices, peaks, t_start: float, keep_trace: Optional[str] = None):
    """Set-up, window and check of one run on ``devices``; returns the
    result line's object and the compared numbers' lines."""
    counter = CompileCounter()
    arrays, g = build_graph(cell.config, seed)
    run_job(cell, g, seed, WARMUP_ROUNDS)
    rounds = int(cell.traffic["rounds"])
    seeds = [seed + j for j in range(window_jobs(cell, seconds))]

    window = Window(traced)
    before = counter.snapshot()
    results, t0, t1 = window.run(
        lambda: [run_job(cell, g, s, rounds) for s in seeds], keep_trace)
    compiles, hits = (a - b for a, b in zip(counter.snapshot(), before))
    setup_s = t0 - t_start
    wall_s = t1 - t0
    log(f"window: {len(seeds)} jobs of {rounds} rounds in {wall_s:.4f} s; {compiles} backend compiles "
        f"and {hits} compilation-cache loads inside it")

    device: Dict[str, Any] = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "memory_peak_bytes": peak_memory(devices),
    }
    metrics: Dict[str, Dict[str, Any]] = {}
    extra: Dict[str, Any] = {}
    if traced:
        from bench import xplane

        trace = window.trace
        view = RunView(
            cell=cell, peaks=peaks, chips=len(devices), rounds=rounds * len(seeds),
            wall_s=wall_s, trace=trace, spans=window.spans,
            marker_perf_ns=window.marker_perf_ns, graph=arrays, results=results,
        )
        metrics = read_per_layer(cell, view)
        device["busy_s"] = sum(xplane.busy_ns(o) for o in trace.ops.values()) / len(trace.ops) / 1e9
        device["window_s"] = trace.window_s
        extra["breakdown"] = breakdown(view)
    else:
        values = {"rounds_per_s": rounds * len(seeds) / wall_s, "setup_s": setup_s}
        for m in cell.end_to_end:
            if cell.reports(m):
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    ok, nums, lines = check(cell, arrays, seeds, rounds, results)
    limits = cell.limits["limits"]
    out = {
        "correct": ok,
        "attempted": len(nums),
        "failed": sum(1 for k, v in nums.items() if k in limits and not v <= limits[k]),
        "metrics": metrics,
        "device": device,
        **extra,
        "jobs": len(seeds),
        "rounds": rounds,
        "window_compiles": compiles,
        "window_cache_loads": hits,
        "checks": {k: {"value": v, "limit": limits.get(k)} for k, v in nums.items()},
    }
    return out, lines


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced run's profile to this directory")
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    try:
        jax = configure_jax()
        devices, peaks = check_devices(jax, cell.chips)
        sys.path.insert(0, str(spec.ROOT / "src"))
        import repro  # noqa: F401  (the system under test)
    except (NoChip, ImportError) as err:
        log(f"cannot run {cell.name}: {err}")
        return EXIT_NO_CHIP
    out, lines = execute(
        cell, seed=args.seed, seconds=args.seconds, traced=bool(args.trace),
        devices=devices, peaks=peaks, t_start=t_start, keep_trace=args.keep_trace,
    )
    for line in lines:
        print(f"bench: check {line}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0
