"""Serving benchmark: the GraphInferenceServer query path under load.

Sweeps scheduler batch size x serving engine x client count over a fixed
synthetic query stream and reports per-cell p50/p99 latency and
throughput (the microbatcher's virtual-arrival / real-compute queue model,
repro.serving.scheduler). The kernel engine column degrades to ``direct``
when Pallas is unavailable — the row records the engine actually used.

Discovered by benchmarks/run.py; also writes the committed repo-root
artifact ``BENCH_serve.json`` on every run.

  PYTHONPATH=src python benchmarks/serve_bench.py [--fast]
"""
from __future__ import annotations

import pathlib
import sys
from typing import Dict, List

if __package__ in (None, ""):  # run as a script: wire repo root + src
    _root = pathlib.Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(_root / "src"))
    sys.path.insert(0, str(_root))

from benchmarks.common import write_bench_root


def run(fast: bool = False, dataset: str | None = None, seed: int = 0,
        backend: str = "vmap") -> List[Dict]:
    import jax
    import numpy as np

    from repro.core import FedGAT, FedGATConfig
    from repro.graphs import make_cora_like
    from repro.serving import (
        GraphInferenceServer,
        MicroBatcher,
        Query,
    )

    dataset = dataset or ("tiny" if fast else "cora_like")
    g = make_cora_like(dataset, seed=seed)
    model_cfg = FedGATConfig()
    params = FedGAT(model_cfg).init(jax.random.PRNGKey(seed), g)

    batch_sizes = (8,) if fast else (4, 16, 64)
    engines = ("direct", "kernel")
    client_counts = (2,) if fast else (2, 8)
    num_queries = 64 if fast else 512
    qps = 2000.0

    rows: List[Dict] = []
    rng = np.random.default_rng(seed)
    for clients in client_counts:
        stream = [
            Query(int(c), int(n))
            for c, n in zip(
                rng.integers(0, clients, size=num_queries),
                rng.integers(0, g.num_nodes, size=num_queries),
            )
        ]
        arrivals = list(np.cumsum(rng.exponential(1.0 / qps, size=num_queries)))
        for engine in engines:
            server = GraphInferenceServer(
                params, model_cfg, g, num_clients=clients, engine=engine,
            )
            server.serve_batch(stream[:1])  # compile + build packs off-clock
            for bs in batch_sizes:
                batcher = MicroBatcher(
                    server.serve_batch, max_batch_size=bs, max_wait=0.005
                )
                batcher.run(stream, arrivals)
                s = batcher.stats.summary()
                rows.append({
                    "dataset": dataset,
                    "engine_requested": engine,
                    "engine": engine,
                    "clients": clients,
                    "max_batch_size": bs,
                    "queries": int(s["queries"]),
                    "batches": int(s["batches"]),
                    "mean_batch": s["mean_batch"],
                    "p50_ms": s["p50_ms"],
                    "p99_ms": s["p99_ms"],
                    "throughput_qps": s["throughput_qps"],
                    "cache_hits": server.cache.stats()["hits"],
                    "cache_misses": server.cache.stats()["misses"],
                })
    write_bench_root("serve", rows)
    return rows


def derived(rows: List[Dict]) -> str:
    best = max(rows, key=lambda r: r["throughput_qps"])
    return (
        f"cells={len(rows)} best={best['throughput_qps']:.0f}qps "
        f"(engine={best['engine']} batch={best['max_batch_size']} "
        f"K={best['clients']}) p99={best['p99_ms']:.2f}ms"
    )


if __name__ == "__main__":
    import argparse
    import time

    from benchmarks.common import csv_row, save_results

    ap = argparse.ArgumentParser(description="serving benchmark")
    ap.add_argument("--fast", action="store_true", help="reduced sweep")
    args = ap.parse_args()
    t0 = time.perf_counter()
    rows = run(fast=args.fast)
    us = (time.perf_counter() - t0) * 1e6
    save_results("serve_bench", rows)
    print("name,us_per_call,derived")
    print(csv_row("serve_bench", us, derived(rows)), flush=True)
