#!/usr/bin/env python3
"""Smoke test: federated FedGAT training and serving on one TPU chip.

    python chip_smoke.py               # one chip: train, check, serve
    python chip_smoke.py --four-chips  # four chips: shard_map vs vmap only

One process, no children. The one-chip run drives the system's main path
through the entry points a user calls:

  1. device   the first JAX device is a TPU, and the Pallas kernels will
              compile for it rather than run in the interpreter;
  2. train    ``Trainer(cfg).run(g)``: ``fedgat`` with the ``kernel`` engine
              at the paper's width (hidden 8, 8 heads, 2 layers, degree 16)
              on the 100,000-node ``sbm_100k`` graph, 8 clients streamed
              through the vmap cohort step, 3 rounds;
  3. oracle   full-graph logits of the trained model from the ``kernel``
              engine against the ``direct`` engine, the jnp oracle;
  4. serve    ``GraphInferenceServer.serve_batch`` answers 64 seeded
              queries, each label checked against phase 3's logits;

and prints, as its last line, one JSON object naming the device. With
``--four-chips`` it runs only the shard_map backend's one-client-per-chip
layout against the vmap backend on one chip.

Times printed here are host-clock smoke numbers, compilation included: they
show that the path runs, and are not a benchmark.
"""
from __future__ import annotations

import argparse
import importlib.metadata
import json
import sys
import time
from dataclasses import replace
from pathlib import Path

ROOT = Path(__file__).resolve().parent

GRAPH = "sbm_100k"
NUM_CLIENTS = 8
ROUNDS = 3
# Cohort lanes on one chip: the largest lane count whose compiled cohort
# step fits the 16 GB of a v5e by memory_analysis() (see CHANGES.md).
LANES = 3
QUERIES = 64
QUERY_BATCH = 16
# Bounds on max |kernel logit - oracle logit|, as a fraction of the oracle's
# largest |logit| (see CHANGES.md for the argument). At "highest" matmul
# precision both engines do f32 math in different summation orders: ~100
# f32 roundings (6e-8 each) over a 16-neighbour, 32-feature, two-layer path.
REL_TOL_HIGHEST = 1e-5
# The kernel engine as training and serving run it, at the default matmul
# precision: one bf16 pass per f32 matmul on TPU, up to 2^-8 relative error
# per product, compounded through the edge scores, the attention
# polynomial and the second layer.
REL_TOL_DEFAULT = 2.0 ** -6

FAILURES: list = []


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", flush=True)
    sys.exit(1)


def check(ok: bool, msg: str) -> None:
    """Record a failed check; the run goes on so that every phase reports,
    and exits non-zero at the end."""
    if not ok:
        print(f"chip_smoke: FAIL: {msg}", flush=True)
        FAILURES.append(msg)


def check_device(count: int):
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"jax {jax.__version__}, libtpu {_version('libtpu')}", flush=True)
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        fail(f"the first JAX device is {dev.platform!r}, not a TPU")
    if len(devices) < count:
        fail(f"{count} chips needed, {len(devices)} found")
    from repro.kernels.ops import resolve_interpret

    if resolve_interpret():
        fail("Pallas would run in interpret mode on the TPU "
             "(is REPRO_PALLAS_INTERPRET set?)")
    return dev


def _version(package: str) -> str:
    try:
        return importlib.metadata.version(package)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


def check_curves(res, rounds: int, tag: str) -> None:
    import numpy as np

    for name in ("val_curve", "test_curve"):
        curve = res[name]
        check(len(curve) == rounds and bool(np.all(np.isfinite(curve))),
              f"{tag}: {name} is {curve}, want {rounds} finite entries")


def train(g, cfg):
    from repro import telemetry
    from repro.federated import Trainer

    telemetry.enable()
    telemetry.reset()
    t0 = time.perf_counter()
    res = Trainer(cfg).run(g)
    wall = time.perf_counter() - t0
    telemetry.disable()
    rounds = [r.dur_ns / 1e9 for r in telemetry.tracer.records if r.name == "round"]
    check_curves(res, cfg.rounds, "train")
    print(f"train: {cfg.method} + {cfg.model.engine} on {GRAPH}, "
          f"K={cfg.num_clients}, lanes={res['cohort']['lanes']}, "
          f"cohorts/round={res['cohort']['cohorts_per_round']}", flush=True)
    print(f"train: val={res['val_curve']} test={res['test_curve']}", flush=True)
    print(f"train smoke timing (host clock, not a metric): run {wall:.1f} s "
          f"incl. compile; rounds {[round(s, 2) for s in rounds]} s", flush=True)
    return res


def oracle_logits(g, model_cfg, params):
    """Full-graph logits: kernel engine at default and at highest matmul
    precision, and the direct engine at highest."""
    import jax
    import jax.numpy as jnp

    from repro.core.engine import get_engine
    from repro.core.fedgat_model import layered_forward

    h, idx, mask = (jnp.asarray(a) for a in (g.features, g.nbr_idx, g.nbr_mask))
    coeffs = jnp.asarray(model_cfg.coeffs(), jnp.float32)

    def logits(engine: str):
        cfg = replace(model_cfg, engine=engine)
        fwd = jax.jit(lambda p, h, i, m: layered_forward(
            get_engine(engine)(cfg), p, coeffs, None, h, i, m))
        return fwd(params, h, idx, mask)

    kern = logits("kernel")
    with jax.default_matmul_precision("highest"):
        kern_hi = logits("kernel")
        ref = logits("direct")
    return jax.device_get((kern, kern_hi, ref))


def check_oracle(kern, kern_hi, ref) -> None:
    import numpy as np

    check(bool(np.all(np.isfinite(kern)) and np.all(np.isfinite(kern_hi))
               and np.all(np.isfinite(ref))), "oracle: non-finite logits")
    scale = float(np.abs(ref).max())
    d_hi = float(np.abs(kern_hi - ref).max())
    d_def = float(np.abs(kern - ref).max())
    agree = float((kern.argmax(-1) == ref.argmax(-1)).mean())
    top2 = np.sort(ref, axis=-1)[:, -2:]
    # Only a node whose oracle margin is under twice the difference can flip.
    close = int((top2[:, 1] - top2[:, 0] < 2 * d_def).sum())
    print(f"oracle: logits max|.| {scale:.6g}; max|kernel - direct| at highest "
          f"precision {d_hi:.6g} (tol {REL_TOL_HIGHEST:g} x scale), at default "
          f"precision {d_def:.6g} (tol {REL_TOL_DEFAULT:g} x scale); argmax "
          f"agreement {agree:.6f} over {len(ref)} nodes, {close} of them with "
          f"an oracle margin under 2 x the default-precision difference",
          flush=True)
    check(d_hi <= REL_TOL_HIGHEST * scale,
          f"oracle: kernel differs from direct by {d_hi:.6g} at highest precision")
    check(d_def <= REL_TOL_DEFAULT * scale,
          f"oracle: kernel at default precision differs from direct by {d_def:.6g}")


def serve(g, model_cfg, params, kern) -> None:
    import numpy as np

    from repro.serving import GraphInferenceServer, Query

    server = GraphInferenceServer(
        params, model_cfg, g, num_clients=NUM_CLIENTS, engine="kernel"
    )
    rng = np.random.default_rng(0)
    queries = [
        Query(int(c), int(n)) for c, n in zip(
            rng.integers(0, NUM_CLIENTS, QUERIES), rng.integers(0, g.num_nodes, QUERIES)
        )
    ]
    t0 = time.perf_counter()
    results = []
    for i in range(0, QUERIES, QUERY_BATCH):
        results += server.serve_batch(queries[i:i + QUERY_BATCH])
    wall = time.perf_counter() - t0
    want = kern.argmax(-1)
    bad = [(r.client, r.node, r.label) for r in results if r.label != int(want[r.node])]
    d = max(float(np.abs(r.logits - kern[r.node]).max()) for r in results)
    forwards = len({q.client for q in queries})
    print(f"serve: {len(results)} queries in {QUERIES // QUERY_BATCH} batches, "
          f"{len(results) - len(bad)} labels agree with the kernel-engine "
          f"logits; max|served - phase-3 logit| {d:.6g}; {forwards} client "
          f"forwards, the other {QUERIES - forwards} answered from the "
          f"per-client logits memo; {wall:.1f} s host clock incl. compile",
          flush=True)
    check(len(results) == QUERIES and not bad,
          f"serve: labels disagree with the kernel-engine logits at {bad[:3]}")


def four_chips(g) -> None:
    """shard_map, one client per chip, against the vmap backend streamed on
    one chip: the checks of tests/test_sharded.py. Both run at "highest"
    matmul precision, so that the comparison sees the layout and not the
    rounding of one bf16 pass, which the two programs' different fusions
    place differently (see CHANGES.md)."""
    import jax
    import numpy as np

    from repro.core import FedGATConfig
    from repro.federated import FederatedConfig, run_federated

    cfg = FederatedConfig(
        method="fedgat", num_clients=4, rounds=ROUNDS, aggregator="fedavg",
        model=FedGATConfig(engine="kernel"),
    )
    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        r_shard = run_federated(g, cfg, backend="shard_map")
        t1 = time.perf_counter()
        r_vmap = run_federated(g, replace(cfg, max_concurrent_clients=LANES),
                               backend="vmap")
        t2 = time.perf_counter()
    check_curves(r_shard, ROUNDS, "shard_map")
    check_curves(r_vmap, ROUNDS, "vmap")
    mesh = r_shard["mesh"]
    print(f"shard_map mesh: {mesh}", flush=True)
    check(mesh is not None and mesh["num_devices"] == 4 and mesh["platform"] == "tpu",
          f"shard_map ran on {mesh}, want 4 tpu devices")
    print(f"shard_map: val={r_shard['val_curve']} test={r_shard['test_curve']}", flush=True)
    print(f"vmap ({r_vmap['cohort']['lanes']} lanes): val={r_vmap['val_curve']} "
          f"test={r_vmap['test_curve']}", flush=True)
    diff = max(float(np.abs(a - b).max()) for a, b in zip(
        jax.tree.leaves(r_shard["params"]), jax.tree.leaves(r_vmap["params"])))
    print(f"max param difference {diff:.6g} (bound 5e-3); smoke timing (host "
          f"clock, not a metric): shard_map {t1 - t0:.1f} s, vmap {t2 - t1:.1f} s "
          f"incl. compile", flush=True)
    for name in ("val_curve", "test_curve"):
        gap = float(np.abs(np.subtract(r_vmap[name], r_shard[name])).max())
        check(gap <= 1e-6, f"{name}: vmap and shard_map differ by {gap}")
    check(diff < 5e-3, f"shard_map and vmap params differ by {diff:.6g}")
    check(set(r_vmap) == set(r_shard),
          f"result schemas differ: {set(r_vmap) ^ set(r_shard)}")
    for k in ("best_val", "best_test", "final_test"):
        check(abs(r_vmap[k] - r_shard[k]) < 1e-6,
              f"{k}: vmap {r_vmap[k]} vs shard_map {r_shard[k]}")
    check(r_vmap["comm"].download_scalars == r_shard["comm"].download_scalars,
          "comm reports differ")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the shard_map one-client-per-chip path "
                    "against vmap on one chip")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no repro package under {ROOT / 'src'}: run from a checkout")
    sys.path.insert(0, str(ROOT / "src"))
    count = 4 if args.four_chips else 1

    from repro.launch.compile_cache import configure_compile_cache

    print(f"compile cache: {configure_compile_cache()}", flush=True)
    dev = check_device(count)

    from repro.core import FedGATConfig
    from repro.federated import FederatedConfig
    from repro.graphs.synthetic import make_sbm

    g = make_sbm(GRAPH, seed=0)
    print(f"graph: {GRAPH} N={g.num_nodes} d={g.feature_dim} "
          f"classes={g.num_classes} B={g.nbr_idx.shape[1]}", flush=True)
    if args.four_chips:
        four_chips(g)
    else:
        cfg = FederatedConfig(
            method="fedgat", num_clients=NUM_CLIENTS, rounds=ROUNDS,
            backend="vmap", max_concurrent_clients=LANES,
            model=FedGATConfig(engine="kernel"),
        )
        res = train(g, cfg)
        kern, kern_hi, ref = oracle_logits(g, cfg.model, res["params"])
        check_oracle(kern, kern_hi, ref)
        serve(g, cfg.model, res["params"], kern)
    if FAILURES:
        fail(f"{len(FAILURES)} check(s) failed")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))


if __name__ == "__main__":
    main()
