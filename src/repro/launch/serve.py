"""Serving launcher — two modes:

  lm     — batched prefill + decode over the transformer model zoo:
           python -m repro.launch.serve --mode lm --arch yi-6b --reduced \
               --batch 2 --prompt-len 16 --gen-len 8
  graph  — federated graph inference (repro.serving): train or load a
           Trainer checkpoint, serve a node-classification query stream
           through the microbatching scheduler, absorb a graph delta, and
           report latency / cache / drift accounting:
           python -m repro.launch.serve --mode graph --fast

``--mode`` defaults to lm so existing invocations keep working.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def run_lm(argv=None) -> None:
    ap = argparse.ArgumentParser(description="LM serving (prefill + decode)")
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args(argv)

    from repro.configs import get_config
    from repro.models import build_model

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    # Independent streams per consumer: reusing one key across init and the
    # synthetic inputs correlates weights with data.
    key = jax.random.PRNGKey(args.seed)
    key, k_params, k_prompt, k_prefix, k_frames = jax.random.split(key, 5)
    params = model.init(k_params)
    B = args.batch
    cache_len = args.prompt_len + args.gen_len + 8

    prompt = jax.random.randint(k_prompt, (B, args.prompt_len), 0, cfg.vocab_size)
    batch = {"tokens": prompt}
    if cfg.family == "vlm":
        batch["prefix"] = jax.random.normal(k_prefix, (B, cfg.prefix_len, cfg.d_model))
    if cfg.is_encdec:
        frames = jax.random.normal(
            k_frames, (B, max(args.prompt_len // cfg.encoder_ratio, 2), cfg.d_model)
        )
        batch["frames"] = frames

    t0 = time.time()
    # cache_len is a static shape parameter: close over it, don't trace it
    prefill = jax.jit(lambda p, b: model.prefill(p, dict(b, cache_len=cache_len)))
    logits, cache = prefill(params, batch)
    print(f"prefill: {args.prompt_len} tokens x {B} in {time.time()-t0:.2f}s")

    decode = jax.jit(model.decode_step)
    tok = jnp.argmax(logits[:, -1, : cfg.vocab_size], axis=-1)[:, None]
    generated = [np.asarray(tok)]
    t0 = time.time()
    for i in range(args.gen_len - 1):
        logits, cache = decode(params, cache, tok)
        lg = logits[:, -1, : cfg.vocab_size]
        if args.temperature > 0:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(sub, lg / args.temperature)[:, None]
        else:
            tok = jnp.argmax(lg, axis=-1)[:, None]
        generated.append(np.asarray(tok))
    dt = time.time() - t0
    gen = np.concatenate(generated, axis=1)
    print(f"decode: {args.gen_len - 1} steps x {B} seqs in {dt:.2f}s "
          f"({(args.gen_len - 1) * B / max(dt, 1e-9):.1f} tok/s)")
    print("generated token ids:\n", gen)


def run_graph(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="federated graph inference (repro.serving)"
    )
    ap.add_argument("--dataset", default="cora_like")
    ap.add_argument("--ckpt", default="",
                    help="serving bundle directory; empty = quick-train one")
    ap.add_argument("--method", default="fedgat", choices=["fedgat", "distgat"])
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=20,
                    help="training rounds when quick-training a checkpoint")
    ap.add_argument("--engine", default=None,
                    choices=["matrix", "vector", "direct", "kernel", "exact"],
                    help="serving engine override (default: checkpoint's)")
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--qps", type=float, default=2000.0,
                    help="mean arrival rate of the synthetic query stream")
    ap.add_argument("--max-batch-size", type=int, default=32)
    ap.add_argument("--max-wait", type=float, default=0.005,
                    help="scheduler deadline (seconds)")
    ap.add_argument("--refresh-threshold", type=float, default=2.0,
                    help="Thm 3.5 logit bound that triggers a pack refresh")
    ap.add_argument("--update-nodes", type=int, default=8,
                    help="new nodes in the demo graph delta (0 = skip)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fast", action="store_true", help="smoke-size run")
    ap.add_argument("--telemetry-dir", default="",
                    help="enable repro.telemetry and write the run artifacts "
                    "(trace.json/metrics.json/manifest.json/events.jsonl) here")
    args = ap.parse_args(argv)
    from repro import telemetry

    if args.telemetry_dir:
        telemetry.enable(args.telemetry_dir)
    if args.fast:
        args.dataset = "tiny"
        args.clients = min(args.clients, 2)
        args.rounds = min(args.rounds, 2)
        args.queries = min(args.queries, 48)
        args.update_nodes = min(args.update_nodes, 4)

    from repro.core import FedGATConfig
    from repro.federated.trainer import FederatedConfig, Trainer
    from repro.graphs import make_cora_like
    from repro.serving import (
        GraphDelta,
        GraphInferenceServer,
        MicroBatcher,
        Query,
        save_bundle,
    )

    g = make_cora_like(args.dataset, seed=args.seed)
    ckpt_dir = args.ckpt
    if not ckpt_dir:
        import tempfile

        cfg = FederatedConfig(
            method=args.method, num_clients=args.clients, rounds=args.rounds,
            seed=args.seed, model=FedGATConfig(),
        )
        t0 = time.time()
        res = Trainer(cfg).run(g)
        print(f"trained: method={args.method} rounds={args.rounds} "
              f"best_test={res['best_test']:.4f} in {time.time()-t0:.1f}s")
        ckpt_dir = tempfile.mkdtemp(prefix="fedgat_serve_")
        save_bundle(ckpt_dir, res["params"], cfg, step=args.rounds)
    server = GraphInferenceServer.from_checkpoint(
        ckpt_dir, g, engine=args.engine, refresh_threshold=args.refresh_threshold,
    )
    print(f"serving: engine={server.cfg.engine} method={server.method} "
          f"clients={server.num_clients} nodes={g.num_nodes}")

    rng = np.random.default_rng(args.seed)
    queries = [
        Query(int(c), int(n))
        for c, n in zip(
            rng.integers(0, server.num_clients, size=args.queries),
            rng.integers(0, g.num_nodes, size=args.queries),
        )
    ]
    arrivals = np.cumsum(rng.exponential(1.0 / args.qps, size=args.queries))
    batcher = MicroBatcher(
        server.serve_batch,
        max_batch_size=args.max_batch_size, max_wait=args.max_wait,
    )
    with telemetry.span("serve_stream", queries=args.queries, qps=args.qps):
        results = batcher.run(queries, arrivals.tolist())
    correct = sum(r.label == int(g.labels[r.node]) for r in results)
    s = batcher.stats.summary()
    print(f"served: {args.queries} queries in {int(s['batches'])} batches "
          f"(mean {s['mean_batch']:.1f}/batch) "
          f"p50={s['p50_ms']:.2f}ms p99={s['p99_ms']:.2f}ms "
          f"throughput={s['throughput_qps']:.0f} qps "
          f"label_match={correct / max(len(results), 1):.3f}")

    if args.update_nodes:
        m = args.update_nodes
        feats = g.features[rng.integers(0, g.num_nodes, size=m)]
        feats = feats + 0.01 * rng.standard_normal(feats.shape).astype(np.float32)
        n_new = g.num_nodes + m
        edges = np.stack([
            np.arange(g.num_nodes, n_new),
            rng.integers(0, g.num_nodes, size=m),
        ], axis=1)
        owners = (
            rng.integers(0, server.num_clients, size=m)
            if server.method == "distgat" else None
        )
        report = server.apply_update(
            GraphDelta(features=feats, edges=edges, owners=owners)
        )
        worst = max(report["drift"].values(), default=0.0)
        print(f"delta: +{report['new_nodes']} nodes +{report['new_edges']} edges "
              f"-> {report['num_nodes']} nodes; worst_eps={worst:.4f} "
              f"refreshed={report['refreshed']}")
        post = server.serve_batch(
            [Query(0, int(n)) for n in range(g.num_nodes, n_new)]
        )
        print(f"post-update: served {len(post)} new-node queries")

    st = server.stats()
    c = st["cache"]
    print(f"cache: entries={c['entries']} hits={c['hits']} misses={c['misses']} "
          f"patches={c['patches']} refreshes={c['refreshes']}")
    if args.telemetry_dir:
        paths = telemetry.write_run(args.telemetry_dir)
        print(f"telemetry: {len(telemetry.tracer.records)} spans -> "
              f"{paths['trace']}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--mode", choices=("lm", "graph"), default="lm")
    args, rest = ap.parse_known_args(argv)
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    (run_graph if args.mode == "graph" else run_lm)(rest)


if __name__ == "__main__":
    main()
