"""Training launcher.

Two modes, matching the paper + assignment:

  graph  — federated FedGAT node classification (the paper's task):
           python -m repro.launch.train graph --dataset cora_like \
               --clients 10 --rounds 100 --engine vector
  lm     — transformer-zoo language-model training on the synthetic
           pipeline (reduced configs on CPU; full configs on a real mesh):
           python -m repro.launch.train lm --arch yi-6b --steps 50 --reduced
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def run_graph(args) -> None:
    from repro.core import FedGATConfig
    from repro.federated import FederatedConfig, run_federated
    from repro.graphs import make_cora_like

    g = make_cora_like(args.dataset, seed=args.seed)
    cfg = FederatedConfig(
        method=args.method,
        num_clients=args.clients,
        beta=args.beta,
        rounds=args.rounds,
        local_steps=args.local_steps,
        lr=args.lr,
        aggregator=args.aggregator,
        seed=args.seed,
        model=FedGATConfig(engine=args.engine, degree=args.degree, basis=args.basis),
    )
    res = run_federated(g, cfg)
    print(f"dataset={args.dataset} method={args.method} clients={args.clients} "
          f"beta={args.beta} engine={args.engine}")
    print(f"best_val={res['best_val']:.4f} best_test={res['best_test']:.4f} "
          f"final_test={res['final_test']:.4f} seconds={res['seconds']:.1f}")
    if res["comm"]:
        print(f"pretrain_comm_scalars={res['comm'].download_scalars} "
              f"cross_client_edges={res['comm'].cross_client_edges}")


def run_lm(args) -> None:
    from repro.checkpoint import save_checkpoint
    from repro.configs import get_config
    from repro.data import make_lm_batches
    from repro.launch.steps import adam_init_f32, make_train_step
    from repro.models import build_model

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    print(f"arch={cfg.name} reduced={args.reduced} params={n_params/1e6:.2f}M")
    opt = jax.tree.map(jnp.zeros_like, adam_init_f32(jax.eval_shape(lambda: params)))
    step_fn = jax.jit(make_train_step(cfg))

    extra = {}
    if cfg.family == "vlm":
        extra["prefix"] = (cfg.prefix_len, cfg.d_model)
    if cfg.is_encdec:
        extra["frames"] = (max(args.seq_len // cfg.encoder_ratio, 2), cfg.d_model)
    batches = make_lm_batches(
        cfg.vocab_size, args.batch, args.seq_len, seed=args.seed,
        prefix=extra.get("prefix"), frames=extra.get("frames"),
    )
    t0 = time.time()
    for step in range(args.steps):
        batch = {k: jnp.asarray(v) for k, v in next(batches).items()}
        params, opt, loss = step_fn(params, opt, batch)
        if step % args.log_every == 0 or step == args.steps - 1:
            dt = time.time() - t0
            toks = (step + 1) * args.batch * args.seq_len
            print(f"step={step} loss={float(loss):.4f} tok/s={toks/dt:.0f}")
    if args.ckpt:
        save_checkpoint(args.ckpt, {"params": params}, step=args.steps)
        print(f"saved checkpoint to {args.ckpt}")


def main() -> None:
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="mode", required=True)

    g = sub.add_parser("graph")
    g.add_argument("--dataset", default="cora_like")
    g.add_argument("--method", default="fedgat", choices=["fedgat", "distgat", "fedgcn"])
    g.add_argument("--clients", type=int, default=10)
    g.add_argument("--beta", type=float, default=1.0)
    g.add_argument("--rounds", type=int, default=100)
    g.add_argument("--local-steps", type=int, default=3)
    g.add_argument("--lr", type=float, default=0.01)
    g.add_argument("--aggregator", default="fedavg")
    g.add_argument("--engine", default="vector",
                   choices=["matrix", "vector", "direct", "kernel", "exact"])
    g.add_argument("--degree", type=int, default=16)
    g.add_argument("--basis", default="power", choices=["power", "chebyshev"])
    g.add_argument("--seed", type=int, default=0)
    g.set_defaults(fn=run_graph)

    l = sub.add_parser("lm")
    l.add_argument("--arch", required=True)
    l.add_argument("--reduced", action="store_true")
    l.add_argument("--steps", type=int, default=20)
    l.add_argument("--batch", type=int, default=4)
    l.add_argument("--seq-len", type=int, default=128)
    l.add_argument("--log-every", type=int, default=5)
    l.add_argument("--seed", type=int, default=0)
    l.add_argument("--ckpt", default="")
    l.set_defaults(fn=run_lm)

    args = ap.parse_args()
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    args.fn(args)


if __name__ == "__main__":
    main()
