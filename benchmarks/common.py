"""Shared benchmark utilities: timing, result persistence, CSV emission,
and the backend-aware CLI used by the figure scripts."""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
from typing import Any, Callable, Dict, List

RESULTS_DIR = pathlib.Path(__file__).parent / "results"
REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]

BACKEND_CHOICES = ("vmap", "shard_map")


def write_bench_root(name: str, rows: List[Dict[str, Any]]) -> pathlib.Path:
    """Write ``BENCH_<name>.json`` at the repo root — the committed,
    per-run benchmark artifact (kernel_bench/serve_bench emit one on every
    run; check_regression validates them alongside benchmarks/results).

    With telemetry enabled, the run's Chrome trace lands next to it as
    ``BENCH_<name>_trace.json`` and every row carries a ``trace`` pointer
    to it (check_regression skips ``*_trace.json`` — it is a trace, not a
    row list)."""
    from repro import telemetry

    path = REPO_ROOT / f"BENCH_{name}.json"
    if telemetry.enabled():
        trace_path = REPO_ROOT / f"BENCH_{name}_trace.json"
        telemetry.export_chrome_trace(str(trace_path))
        rows = [dict(r, trace=trace_path.name) for r in rows]
    path.write_text(json.dumps(rows, indent=1, default=str) + "\n")
    return path


def request_host_devices(n: int) -> None:
    """Make >= n devices available for the shard_map backend (one client per
    device). Delegates to the launch helper: forces
    ``--xla_force_host_platform_device_count`` (the flag is read lazily at
    backend initialisation, so this works until the first jax device use,
    not merely the first ``import jax``); a pre-existing smaller count in
    XLA_FLAGS is raised to ``n``, never lowered."""
    from repro.launch.multiprocess import force_host_device_count

    force_host_device_count(n)


def figure_cli(
    run: Callable[..., List[Dict[str, Any]]],
    derived: Callable[[List[Dict[str, Any]]], str],
    name: str,
    max_clients: Callable[[bool], int],
    argv: List[str] | None = None,
    default_dataset: str = "cora_like",
) -> None:
    """Shared ``--backend``-aware entry point for the figure scripts.

    Parses the common flags, forces enough host devices for shard_map
    BEFORE jax initialises (the figure scripts defer their repro imports
    into ``run()`` for exactly this reason), then runs, saves and prints.
    """
    ap = argparse.ArgumentParser(description=f"benchmark {name}")
    ap.add_argument("--backend", choices=BACKEND_CHOICES, default="vmap",
                    help="federated Trainer backend (default: vmap)")
    ap.add_argument("--processes", type=int, default=1,
                    help="shard_map only: spread the client mesh over this "
                    "many cooperating OS processes (repro.launch.multiprocess"
                    "; every swept client count must divide evenly)")
    ap.add_argument("--fast", action="store_true", help="reduced sweeps")
    ap.add_argument("--dataset", default=default_dataset)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    process_id = 0
    if args.processes > 1 and args.backend != "shard_map":
        ap.error("--processes > 1 requires --backend shard_map")
    if args.processes > 1 and max_clients(args.fast) % args.processes:
        ap.error(
            f"client count {max_clients(args.fast)} does not divide evenly "
            f"over {args.processes} processes (every process hosts an equal "
            "client block)"
        )
    if args.backend == "shard_map":
        if args.processes > 1:
            from repro.launch.multiprocess import (
                initialize_worker,
                launch_self,
                worker_env_active,
            )

            if not worker_env_active():
                # Launcher side: re-exec this figure script as N workers;
                # the children land here again with the worker env set.
                base = sys.argv if argv is None else [sys.argv[0], *argv]
                per = -(-max_clients(args.fast) // args.processes)
                raise SystemExit(
                    launch_self(base, processes=args.processes,
                                devices_per_process=per)
                )
            process_id, _ = initialize_worker()
        else:
            request_host_devices(max_clients(args.fast))
    from repro import telemetry
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()

    t0 = time.perf_counter()
    with telemetry.span("benchmark", figure=name, backend=args.backend,
                        fast=args.fast):
        rows = run(fast=args.fast, dataset=args.dataset, seed=args.seed,
                   backend=args.backend)
    us = (time.perf_counter() - t0) * 1e6
    if process_id != 0:
        return  # only process 0 persists and reports
    out_name = f"{name}_{args.backend}" if args.backend != "vmap" else name
    if args.processes > 1:
        out_name = f"{out_name}_p{args.processes}"
    save_results(out_name, rows)
    print("name,us_per_call,derived")
    print(csv_row(out_name, us, derived(rows)), flush=True)


def save_results(name: str, rows: List[Dict[str, Any]]) -> None:
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    (RESULTS_DIR / f"{name}.json").write_text(json.dumps(rows, indent=1, default=str))


def timed(fn: Callable, *args, repeats: int = 3, **kwargs):
    """Returns (result, best_us_per_call)."""
    best = float("inf")
    out = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        best = min(best, (time.perf_counter() - t0) * 1e6)
    return out, best


def csv_row(name: str, us: float, derived: str) -> str:
    return f"{name},{us:.1f},{derived}"
