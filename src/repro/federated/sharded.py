"""shard_map federated backend: clients mapped onto a mesh axis.

This is the TPU-native realisation of the paper's communication pattern
(DESIGN.md §3): each device shard holds ONE client's state; the only
collectives crossing the client axis are

  * one ``all_gather`` equivalent at setup (the pre-training pack is
    computed once and replicated — the single communication round),
  * a weighted ``lax.psum`` over the client axis per aggregation round
    (FedAvg / FedProx / the client mean feeding server-side FedAdam), and
  * a scalar ``psum`` broadcasting the round's evaluation metrics, which
    are computed on shard 0 only.

No feature tensors cross clients during training — exactly the paper's
guarantee — and the whole R-round schedule compiles into a single XLA
program with a ``lax.scan`` over rounds.

Feature parity with the vmap backend (trainer.py):

  * every aggregator (fedavg / fedprox / fedadam) — the server Adam state
    is replicated into every shard and threaded through the scan carry;
    since the weighted ``psum`` mean is identical on all shards, the
    replicated states never diverge;
  * client subsampling (Algorithm 2's CS(t)) — the 0/1 participation
    weights are precomputed host-side by the SAME
    :func:`~repro.federated.trainer.selection_schedule` the vmap backend
    uses and scanned as a ``(rounds, K)`` array sharded over the client
    axis; an unselected shard contributes zero weight to the ``psum`` and
    keeps its optimizer state.

This backend is reached through the unified entry
(``run_federated(g, cfg, backend="shard_map")`` / ``Trainer``); it shares
the model construction, local-update math and result schema with the vmap
backend, and tests assert the two produce identical metric trajectories
for every (aggregator, client_fraction) combination.

Multi-process execution
-----------------------
After ``jax.distributed.initialize`` the SAME code runs as a multi-
controller SPMD program: ``_client_mesh`` lays the client axis over the
**global** device list (an equal, contiguous block of clients per process)
and the input placement switches from plain host arrays to global
``jax.Array``s built with ``jax.make_array_from_callback`` — each process
materialises only the client shards it can address (its own clients'
neighbour/train masks), while replicated operands (params, server state,
the CS(t) table) are mirrored on every process from the same host-side
computation. The psum aggregation, CS(t) selection, DP noise streams and
secure-aggregation masks are all keyed by the *global* client axis index,
so trajectories are process-layout-independent: a 2-process × 2-device run
matches the 1-process × 4-device run that the parity tests pin down.
``repro.launch.multiprocess`` is the launcher that sets this up on CPU.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import telemetry
from repro._compat.jax_compat import shard_map
from repro.federated.aggregation import fedadam_update
from repro.federated.partition import (
    ClientSubgraph,
    Partition,
    client_neighbor_masks,
    client_subgraph,
    client_train_masks,
    dirichlet_partition,
)
from repro.federated.trainer import (
    FederatedConfig,
    accuracies,
    build_forward,
    build_result,
    client_masks,
    make_local_update,
    make_loss_fn,
    run_federated,
    selection_schedule,
)
from repro.graphs.graph import Graph
from repro.optim.adamw import adam_init
from repro.privacy import (
    add_client_mask,
    client_round_key,
    mask_base_key,
    noise_base_key,
)


def _client_mesh(num_clients: int) -> Mesh:
    """One device per client over the *global* device list.

    Single-process: the first ``num_clients`` devices, as before. Multi-
    process (after ``jax.distributed.initialize``): an equal block of
    ``num_clients / num_processes`` devices from every process, in process
    order — client k lives on process ``k // (K / P)``, so each process
    hosts a contiguous block and the data placement below can materialise
    exactly those shards.
    """
    devs = jax.devices()
    nproc = jax.process_count()
    if nproc <= 1:
        if len(devs) < num_clients:
            raise ValueError(
                f"need >= {num_clients} devices for {num_clients} clients, have "
                f"{len(devs)} (set XLA_FLAGS=--xla_force_host_platform_device_count=...)"
            )
        return Mesh(np.array(devs[:num_clients]), ("clients",))
    if num_clients % nproc:
        raise ValueError(
            f"num_clients={num_clients} must divide evenly over "
            f"{nproc} processes (every process hosts an equal client block)"
        )
    per = num_clients // nproc
    by_proc: Dict[int, list] = {}
    for d in devs:
        by_proc.setdefault(d.process_index, []).append(d)
    chosen = []
    for p in sorted(by_proc):
        local = by_proc[p]
        if len(local) < per:
            raise ValueError(
                f"process {p} has {len(local)} devices but hosts {per} of "
                f"{num_clients} clients (launch with --devices-per-process "
                f">= {per})"
            )
        chosen.extend(local[:per])
    return Mesh(np.array(chosen), ("clients",))


def _spans_processes(mesh: Mesh) -> bool:
    return len({d.process_index for d in mesh.devices.flat}) > 1


def _put_global(mesh: Mesh, spec: P, value) -> jax.Array:
    """Build a global ``jax.Array`` for one shard_map operand from host data
    every process computed identically; the callback hands each process only
    the index slices it can address."""
    arr = np.asarray(value)
    sharding = NamedSharding(mesh, spec)
    return jax.make_array_from_callback(arr.shape, sharding, lambda idx: arr[idx])


def _replicate_tree(mesh: Mesh, tree):
    """Mirror a (host-identical) pytree as fully-replicated global arrays."""
    return jax.tree.map(lambda x: _put_global(mesh, P(), x), tree)


def _stacked_client_input(
    mesh: Mesh, build: Callable[[int], np.ndarray], shape_tail: Tuple[int, ...]
) -> jax.Array:
    """Global ``(K, *shape_tail)`` array, one client per device on the
    ``clients`` axis. ``build(k)`` produces client k's slice and is invoked
    only for the clients this process hosts — the multi-process data
    placement: no process ever materialises another process's shards."""
    K = int(mesh.devices.size)
    sharding = NamedSharding(mesh, P("clients"))

    def cb(idx):
        k = idx[0].start or 0
        return np.asarray(build(k))[None]

    return jax.make_array_from_callback((K,) + tuple(shape_tail), sharding, cb)


def addressable_clients(mesh: Mesh) -> list:
    """Client ids (positions on the ``clients`` axis) whose shards this
    process can address — the set a process is allowed to load data for."""
    me = jax.process_index()
    return [
        k for k, d in enumerate(mesh.devices.flat) if d.process_index == me
    ]


def process_client_subgraphs(
    g: Graph, part: Partition, mesh: Mesh, hops: int = 1
) -> Dict[int, ClientSubgraph]:
    """Per-process graph loading: the local-subgraph (owned nodes +
    ``hops``-hop halo) of every client this process addresses, extracted
    via CSR frontier expansion. Nothing O(N^2) and nothing belonging to
    another process's clients is ever materialised — a process's resident
    graph bytes are proportional to its own clients' subgraphs, not to the
    global graph count times K."""
    return {
        k: client_subgraph(g, part, k, hops) for k in addressable_clients(mesh)
    }


def _client_mask_builders(cfg: FederatedConfig, g: Graph, part: Partition):
    """Per-client (nb_mask, tr_mask) builders mirroring
    :func:`~repro.federated.trainer.client_masks` one client at a time."""
    if cfg.method == "distgat":
        nb = lambda k: client_neighbor_masks(g, part, clients=[k])[0]
    else:
        nb = lambda k: g.nbr_mask
    tr = lambda k: client_train_masks(g, part, clients=[k])[0]
    return nb, tr


def _run_shard_map(g: Graph, cfg: FederatedConfig, mesh: Mesh | None = None) -> Dict[str, Any]:
    """FedGAT/DistGAT/FedGCN rounds with clients sharded over a mesh axis."""
    from repro.federated.cohort import cohort_active, run_cohort_rounds

    K = cfg.num_clients

    if cohort_active(cfg):
        # Cohort streaming requested: the mesh covers DEVICES (lanes), not
        # clients, and cohorts of clients stream through it (cohort.py).
        return run_cohort_rounds(g, cfg, backend="shard_map", mesh=mesh)
    if (
        cfg.rounds > 0
        and mesh is None
        and jax.process_count() <= 1
        and len(jax.devices()) < K
    ):
        # More clients than devices: the one-client-per-shard layout cannot
        # exist, so stream device-sized cohorts instead of failing.
        return run_cohort_rounds(g, cfg, backend="shard_map")

    t0 = time.time()
    key = jax.random.PRNGKey(cfg.seed)
    k_pack, k_init = jax.random.split(key)
    part = dirichlet_partition(g.labels, K, cfg.beta, cfg.seed)

    init_fn, forward, data = build_forward(cfg, g, k_pack)
    global_params = init_fn(k_init)

    if cfg.rounds == 0:
        # Pure setup/accounting (fig3's path): the partition, pack and comm
        # report need no devices, so don't require a K-device mesh.
        return build_result(
            cfg=cfg, params=global_params, val_curve=[], test_curve=[],
            part=part, g=g, seconds=time.time() - t0, mesh=mesh,
        )

    if mesh is None:
        mesh = _client_mesh(K)
    multiprocess = _spans_processes(mesh)
    server_state = adam_init(global_params)
    sel, _ = selection_schedule(cfg)          # (rounds, K) — CS(t) weights

    if multiprocess:
        # Multi-controller placement: every operand becomes a global array;
        # the per-client masks are materialised ONLY for this process's
        # addressable client shards.
        nb_build, tr_build = _client_mask_builders(cfg, g, part)
        nb_masks = _stacked_client_input(mesh, nb_build, g.nbr_mask.shape)
        tr_masks = _stacked_client_input(mesh, tr_build, g.train_mask.shape)
        sel_sharded = _put_global(mesh, P(None, "clients"), sel)
        sel_full = _put_global(mesh, P(), sel)
        global_params = _replicate_tree(mesh, global_params)
        server_state = _replicate_tree(mesh, server_state)
        data = _replicate_tree(mesh, data)
    else:
        # Single-process: plain host arrays, exactly the pre-existing path
        # (jit places them), keeping single-host runs bit-identical.
        nb_masks, tr_masks = client_masks(cfg, g, part)
        sel_sharded = sel_full = jnp.asarray(sel)
        data = jax.device_put(data, NamedSharding(mesh, P()))

    local_update = make_local_update(make_loss_fn(forward), cfg)
    priv = cfg.privacy
    noise_base = noise_base_key(cfg.seed)
    mask_base = mask_base_key(cfg.seed)

    def shard_body(nb_masks_s, tr_masks_s, sel_s, sel_full, gparams, srv_state,
                   data):
        """Runs on one shard = one client. Leading client axis is size 1.

        ``sel_full`` is the replicated (rounds, K) CS(t) table: each shard
        reads its own column for participation and — with secure_agg on —
        the whole row to decide which pairwise masks are live this round.
        """
        nb_mask = nb_masks_s[0]
        tr_mask = tr_masks_s[0]
        my_sel = sel_s[:, 0]                  # (rounds,) this client's CS(t)
        cid = jax.lax.axis_index("clients")
        opt_state = adam_init(gparams)

        def round_fn(carry, xs):
            w, t, sel_row = xs
            gp, opt, srv = carry
            noise_key = client_round_key(noise_base, t, cid)
            local_params, new_opt = local_update(
                gp, opt, data, nb_mask, tr_mask, noise_key
            )
            if priv.secure_agg:
                # Ship a masked update: the same deterministic pairwise
                # masks the vmap backend adds, cancelling in the psum.
                local_params = add_client_mask(
                    mask_base, t, cid, sel_row, local_params, priv.mask_scale
                )
            # An unselected shard keeps its optimizer state (same rule as
            # the vmap backend's scatter of selected states only).
            opt = jax.tree.map(
                lambda new, old: jnp.where(w > 0, new, old), new_opt, opt
            )
            # The ONLY training-time cross-client collective: weighted mean
            # of the participating clients' params.
            den = jax.lax.psum(w, "clients")
            mean = jax.tree.map(
                lambda p: jax.lax.psum(w * p, "clients") / den, local_params
            )
            if cfg.aggregator == "fedadam":
                new_global, srv = fedadam_update(gp, mean, srv, cfg.server_lr)
            else:
                new_global = mean
            # Evaluation: new_global is replicated, so the full-graph
            # forward is identical on every shard — run it on shard 0 only
            # and broadcast the two scalars with a psum.
            def do_eval(_):
                return accuracies(forward, new_global, data)

            def skip_eval(_):
                return jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)

            va, ta = jax.lax.cond(
                jax.lax.axis_index("clients") == 0, do_eval, skip_eval, None
            )
            va = jax.lax.psum(va, "clients")
            ta = jax.lax.psum(ta, "clients")
            return (new_global, opt, srv), (va, ta)

        (gp, _, _), (vas, tas) = jax.lax.scan(
            round_fn,
            (gparams, opt_state, srv_state),
            (my_sel, jnp.arange(my_sel.shape[0], dtype=jnp.int32), sel_full),
        )
        return gp, vas, tas

    spec_clients = P("clients")
    fn = jax.jit(
        shard_map(
            shard_body,
            mesh=mesh,
            in_specs=(spec_clients, spec_clients, P(None, "clients"), P(), P(), P(),
                      P()),
            out_specs=(P(), P(), P()),
        )
    )
    # All rounds run inside ONE jitted lax.scan, so per-round spans cannot
    # exist on this path — a single span covers the whole scan.
    with telemetry.span("rounds_scan", rounds=cfg.rounds, backend="shard_map"):
        gp, vas, tas = fn(
            nb_masks, tr_masks, sel_sharded, sel_full, global_params,
            server_state, data,
        )
        vas, tas = np.asarray(vas), np.asarray(tas)
    val_curve = [float(x) for x in np.asarray(vas)]
    test_curve = [float(x) for x in np.asarray(tas)]
    return build_result(
        cfg=cfg, params=gp, val_curve=val_curve, test_curve=test_curve,
        part=part, g=g, seconds=time.time() - t0, mesh=mesh,
    )


def run_federated_sharded(g: Graph, cfg: FederatedConfig, mesh: Mesh | None = None) -> Dict[str, Any]:
    """Backwards-compatible wrapper for the shard_map backend."""
    return run_federated(g, cfg, backend="shard_map", mesh=mesh)
