"""Plain reference of a synchronous federated job (FedGAT paper,
Algorithm 2): a Dirichlet(beta) split of the nodes over K clients
(Hsu, Qi & Brown 2019), CS(t) selection of a client fraction, each selected
client taking ``local_steps`` Adam steps (its own optimizer state kept
between rounds) on the cross-entropy of its training nodes, then FedAvg,
and a full-graph evaluation of the new global model every round.

It imports nothing of ``repro``; the model is a module of this package
(``init``/``prepare``/``forward``). ``run`` follows the job round by
round on the default device and returns every round's global parameters.
"""
from __future__ import annotations

import importlib
import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


def model_module(name: str):
    return importlib.import_module(f"bench.reference.{name}")


def dirichlet_owner(labels: np.ndarray, k: int, beta: float, seed: int) -> np.ndarray:
    """(N,) client id per node: each class split ~ Dir(beta), floor counts,
    the remainder dealt round-robin over the largest shares."""
    rng = np.random.default_rng(seed)
    owner = np.zeros(labels.shape[0], dtype=np.int32)
    for c in np.unique(labels):
        idx = np.nonzero(labels == c)[0]
        rng.shuffle(idx)
        props = rng.dirichlet(np.full(k, beta))
        counts = np.floor(props * len(idx)).astype(int)
        order = np.argsort(-props)
        for i in range(len(idx) - counts.sum()):
            counts[order[i % k]] += 1
        start = 0
        for j in range(k):
            owner[idx[start : start + counts[j]]] = j
            start += counts[j]
    return owner


def selected(k: int, fraction: float, rounds: int, seed: int) -> np.ndarray:
    """(rounds, n_sel) client ids of CS(t): half-up rounding of fraction x K,
    all clients in order at fraction 1, else a fresh draw per round."""
    n_sel = min(k, max(1, int(math.floor(fraction * k + 0.5))))
    if n_sel >= k:
        return np.broadcast_to(np.arange(k, dtype=np.int32), (rounds, k)).copy()
    rng = np.random.default_rng(seed + 1)
    return np.stack([rng.choice(k, size=n_sel, replace=False) for _ in range(rounds)])


def cross_entropy(logits, labels, mask):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    w = mask.astype(jnp.float32)
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)


def accuracy(logits, labels, mask):
    hit = (jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32)
    w = mask.astype(jnp.float32)
    return jnp.sum(hit * w) / jnp.maximum(jnp.sum(w), 1.0)


def adam(grads, state, params, lr, wd):
    """One Adam step with L2 weight decay added to the update, in the
    parameters' type."""
    step = state["step"] + 1
    t = step.astype(jnp.float32)
    mu = jax.tree.map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g, state["mu"], grads)
    nu = jax.tree.map(lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g, state["nu"], grads)
    dtype = jax.tree.leaves(params)[0].dtype
    c1, c2 = (1 - ADAM_B1 ** t).astype(dtype), (1 - ADAM_B2 ** t).astype(dtype)
    new = jax.tree.map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + ADAM_EPS) + wd * p),
        params, mu, nu,
    )
    return new, {"step": step, "mu": mu, "nu": nu}


def run(config: Dict, job: Dict, graph: Dict, seed: int, rounds: int,
        dtype=jnp.float32) -> Dict:
    """Follow ``rounds`` rounds of the job from ``seed``.

    Returns ``params`` (rounds + 1 host pytrees: the initial global model,
    then each round's), ``val``/``test`` accuracy per round, ``owner``,
    ``logits`` (params -> this reference's (N, C) class logits on the
    host, float64), ``labels``, ``train_mask``, and
    ``grad_norms``: per leaf, the mean over the first round's clients of
    the norm of their first local gradient (the leaf-exclusion rule of
    ``bench.compare``).
    ``dtype`` is the type of the parameters, features, activations and
    Adam state (float32; bfloat16 for the control); the loss is taken in
    float32.
    """
    if job.get("aggregator", "fedavg") != "fedavg":
        raise ValueError("the reference covers FedAvg")
    if job.get("aggregation_mode", "sync") != "sync" or job.get("privacy"):
        raise ValueError("the reference covers sync rounds without privacy")
    mod = model_module(config["reference"])
    program = config["program"]
    k = int(job["num_clients"])
    lr, wd, steps = float(job["lr"]), float(job["weight_decay"]), int(job["local_steps"])

    owner = dirichlet_owner(graph["labels"], k, float(job["beta"]), seed)
    train = np.stack([(owner == j) & graph["train_mask"] for j in range(k)])
    chosen = selected(k, float(job["client_fraction"]), rounds, seed)

    # The graph goes into each jitted call as an argument, not as a
    # constant the compiler would fold.
    prep = mod.prepare(graph, program, dtype)
    labels = jnp.asarray(graph["labels"])
    val_mask, test_mask = jnp.asarray(graph["val_mask"]), jnp.asarray(graph["test_mask"])
    _, k_init = jax.random.split(jax.random.PRNGKey(seed))
    gparams = mod.init(k_init, graph["features"].shape[1], int(graph["num_classes"]), program)
    gparams = jax.tree.map(lambda a: a.astype(dtype), gparams)

    def loss(params, prep, labels, tr):
        return cross_entropy(mod.forward(params, prep, program), labels, tr)

    grad = jax.grad(loss)

    @jax.jit
    def clients(gp, opts, trs, prep, labels):
        def one(args):
            opt, tr = args

            def step(carry, _):
                p, o = carry
                g = grad(p, prep, labels, tr)
                norms = jax.tree.map(lambda a: jnp.sqrt(jnp.sum(a * a)), g)
                return adam(g, o, p, lr, wd), norms

            (p, o), norms = jax.lax.scan(step, (gp, opt), None, length=steps)
            return p, o, jax.tree.map(lambda a: a[0], norms)

        return jax.lax.map(one, (opts, trs))

    forward = jax.jit(lambda params, prep: mod.forward(params, prep, program))

    @jax.jit
    def evaluate(params, prep, labels, val_mask, test_mask):
        logits = mod.forward(params, prep, program)
        return accuracy(logits, labels, val_mask), accuracy(logits, labels, test_mask)

    zeros = jax.tree.map(jnp.zeros_like, gparams)
    opts = {
        "step": jnp.zeros((k,), jnp.int32),
        "mu": jax.tree.map(lambda a: jnp.zeros((k,) + a.shape, a.dtype), zeros),
        "nu": jax.tree.map(lambda a: jnp.zeros((k,) + a.shape, a.dtype), zeros),
    }
    trs = jnp.asarray(train)
    grad_norms = None
    history: List = [jax.device_get(gparams)]
    val, test = [], []
    for t in range(rounds):
        ids = jnp.asarray(chosen[t])
        sel_opts = jax.tree.map(lambda a: a[ids], opts)
        new_p, new_o, norms = clients(gparams, sel_opts, trs[ids], prep, labels)
        if grad_norms is None:
            grad_norms = jax.device_get(jax.tree.map(jnp.mean, norms))
        opts = jax.tree.map(lambda a, b: a.at[ids].set(b), opts, new_o)
        gparams = jax.tree.map(lambda a: jnp.mean(a, axis=0), new_p)
        va, ta = evaluate(gparams, prep, labels, val_mask, test_mask)
        history.append(jax.device_get(gparams))
        val.append(float(va))
        test.append(float(ta))
    return {"params": history, "val": val, "test": test, "owner": owner,
            "grad_norms": grad_norms,
            "labels": graph["labels"], "train_mask": graph["train_mask"],
            "logits": lambda p: np.asarray(forward(
                jax.tree.map(lambda a: jnp.asarray(a, dtype), p), prep), np.float64)}
