"""Unified federated training entry (paper Algorithm 2).

The simulation is *protocol-faithful*: what distinguishes clients is (a)
which training labels they hold and (b) which edges they may see —
FedGAT/FedGCN clients see cross-client information only through the
pre-training communication (packs / exact aggregates), DistGAT clients have
cross-client edges dropped. Local updates run on every client in parallel,
followed by FedAvg/FedProx/FedAdam aggregation.

Two execution backends realise the same schedule (``FederatedConfig.backend``):
  vmap       — clients stacked on a batch axis of one device (default)
  shard_map  — one client per device shard on a mesh axis (sharded.py)

Both are driven through :class:`Trainer` (``run_federated`` is a thin
wrapper) and return the same result schema; the local-update math
(:func:`make_local_update`), model construction (:func:`build_forward`) and
best-checkpoint rule (:func:`best_metrics`) are shared, so the backends
cannot drift apart.

Supported methods:
  fedgat   — the paper's algorithm (engine: any registered layer-1 engine)
  distgat  — GAT, cross-client edges dropped, FedAvg (baseline)
  fedgcn   — FedGCN (Yao et al. 2023): exact pre-communicated aggregates,
             i.e. mathematically a GCN on the full graph with local losses
  gat/gcn  — centralised baselines via train_centralized()
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass, field, replace
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.core.fedgat_model import FedGAT, FedGATConfig, layered_forward
from repro.core.gat import masked_accuracy, masked_cross_entropy
from repro.core.gcn import gcn_forward_nbr, init_gcn_params, normalized_nbr_coeffs
from repro.core.nbr_gather import NbrTable, reverse_slots
from repro.federated import comm as comm_mod
from repro.federated.aggregation import fedadam_server, fedavg, fedprox_grad
from repro.federated.partition import (
    Partition,
    client_neighbor_masks,
    client_train_masks,
    dirichlet_partition,
)
from repro.graphs.graph import Graph
from repro.optim.adamw import adam_init, adam_update
from repro.privacy import (
    PrivacyConfig,
    add_client_mask,
    client_round_key,
    make_dp_transform,
    mask_base_key,
    node_influence_bound,
    noise_base_key,
    noisy_pack,
    pack_noise_key,
    privacy_report,
)
from repro.telemetry.manifest import build_manifest

Array = jax.Array

BACKENDS = ("vmap", "shard_map")

# Count XLA compiles into the run manifest (idempotent; host-side only).
telemetry.install_jax_hooks()


@dataclass(frozen=True)
class FederatedConfig:
    method: str = "fedgat"            # fedgat | distgat | fedgcn
    backend: str = "vmap"             # vmap | shard_map
    num_clients: int = 10
    beta: float = 1.0                 # Dirichlet: 1 = non-iid, 1e4 = iid
    rounds: int = 60
    local_steps: int = 3
    lr: float = 0.01
    weight_decay: float = 1e-3
    aggregator: str = "fedavg"        # fedavg | fedprox | fedadam
    prox_mu: float = 0.01
    server_lr: float = 0.05
    client_fraction: float = 1.0      # Algorithm 2's CS(t) subset sampling
    seed: int = 0
    model: FedGATConfig = field(default_factory=FedGATConfig)
    gcn_hidden: int = 16
    privacy: PrivacyConfig = field(default_factory=PrivacyConfig)
    # Cohort streaming (federated/cohort.py): decouple clients from devices.
    max_concurrent_clients: Optional[int] = None   # cohort size cap (None = one lane per client)
    aggregation_mode: str = "sync"    # sync | buffered (staleness-weighted)
    staleness_power: float = 0.5      # buffered: λ(s) = (1 + s)^(-power)
    churn_drop_rate: float = 0.0      # buffered: P(selected client drops mid-round)
    churn_join_rate: float = 0.0      # buffered: P(unselected client joins mid-round)


# ---------------------------------------------------------------------------
# Shared building blocks (both backends use exactly these)
# ---------------------------------------------------------------------------

def pack_released(cfg: FederatedConfig) -> bool:
    """True when this run pre-communicates a pack (the payload pack-DP
    noises): a fedgat/distgat method whose effective engine needs one."""
    from repro.core.engine import get_engine

    if cfg.method not in ("fedgat", "distgat"):
        return False
    return get_engine(method_model_config(cfg).engine).needs_pack


def method_model_config(cfg: FederatedConfig) -> FedGATConfig:
    """The model config a federated method actually trains.

    DistGAT is the same architecture with the exact layer-1 engine — derived
    with ``dataclasses.replace`` so every other field (num_layers,
    leaky_slope, r, ...) is preserved.
    """
    if cfg.method == "distgat":
        return replace(cfg.model, engine="exact")
    return cfg.model


def graph_data(g: Graph) -> Dict[str, Array]:
    """The graph's device arrays every jitted step takes as an argument."""
    return {
        "h": jnp.asarray(g.features),
        "nbr_idx": jnp.asarray(g.nbr_idx),
        "nbr_mask": jnp.asarray(g.nbr_mask),
        "labels": jnp.asarray(g.labels),
        "val_mask": jnp.asarray(g.val_mask),
        "test_mask": jnp.asarray(g.test_mask),
    }


def build_forward(
    cfg: FederatedConfig, g: Graph, key: Array
) -> Tuple[Callable, Callable, Dict[str, Any]]:
    """Returns (init_fn, forward(params, data, nbr_mask) -> logits, data).

    ``data`` is :func:`graph_data` plus what the method's forward reads
    (series coefficients, the one-shot pack and the graph's reverse-slot
    tables ``nbr_table``, or the GCN coefficients).
    Every jitted step takes it as an argument: closed over, the graph would
    be baked into each compiled program as constants, which at 1e5 nodes
    makes compiles take minutes.

    For fedgat/distgat this builds a :class:`FedGAT` facade (coefficients
    computed once; the one-shot pack communicated here, under ``key``).
    With ``privacy.pack_noise_multiplier > 0`` the stored pack is replaced
    by its noised release (privacy/pack_dp.py) — the one-shot Gaussian
    mechanism on the only raw-feature-derived payload that leaves a client.
    """
    data = graph_data(g)
    if cfg.method in ("fedgat", "distgat"):
        model = FedGAT(method_model_config(cfg))
        model.precommunicate(key, g)
        if cfg.privacy.pack_noise_multiplier > 0 and model.pack is not None:
            # Node-level accounting calibrates to the node-influence bound
            # of the (degree-capped) neighbour lists; edge-level (the
            # default) to a single neighbour term.
            granularity = (
                "node" if cfg.privacy.dp_granularity == "node" else "edge"
            )
            influence = (
                node_influence_bound(g) if granularity == "node" else 1
            )
            model.pack = noisy_pack(
                pack_noise_key(cfg.seed), model.pack,
                data["h"], cfg.privacy.pack_noise_multiplier,
                granularity=granularity, node_influence=influence,
            )
        # The reverse-slot tables of the graph's concrete lists: with them
        # the neighbour gathers' backward is a gather, not a scatter-add.
        rev, overflow = reverse_slots(g.nbr_idx, g.nbr_mask)
        data.update(
            coeffs=model.coeffs, pack=model.pack,
            nbr_table=NbrTable(data["nbr_mask"], jnp.asarray(rev), jnp.asarray(overflow)),
        )

        def init_fn(k):
            return model.init(k, g)

        def forward(params, data, nb_mask):
            return layered_forward(
                model.engine, params, data["coeffs"], data["pack"],
                data["h"], data["nbr_idx"], nb_mask, data["nbr_table"],
            )

        return init_fn, forward, data
    if cfg.method == "fedgcn":
        data["coef"] = jnp.asarray(normalized_nbr_coeffs(g.nbr_idx, g.nbr_mask))

        def init_fn(k):
            return init_gcn_params(k, g.feature_dim, cfg.gcn_hidden, g.num_classes)

        def forward(params, data, nb_mask):  # nb_mask unused: aggregates are exact
            return gcn_forward_nbr(params, data["h"], data["nbr_idx"], data["coef"])

        return init_fn, forward, data
    raise ValueError(f"unknown federated method {cfg.method!r}")


def client_masks(cfg: FederatedConfig, g: Graph, part: Partition):
    """Per-client (edge-visibility, train-label) masks: (K, N, B), (K, N)."""
    K = cfg.num_clients
    if cfg.method == "distgat":
        nb_masks = jnp.asarray(client_neighbor_masks(g, part))
    else:
        nb_masks = jnp.broadcast_to(
            jnp.asarray(g.nbr_mask)[None], (K,) + g.nbr_mask.shape
        )
    return nb_masks, jnp.asarray(client_train_masks(g, part))


def make_loss_fn(forward: Callable) -> Callable:
    """Client objective shared by both backends: masked CE on the client's
    training labels under its edge-visibility mask."""

    def loss_fn(params, data, nb_mask, tr_mask):
        logits = forward(params, data, nb_mask)
        with jax.named_scope("loss"):
            return masked_cross_entropy(logits, data["labels"], tr_mask)

    return loss_fn


def accuracies(forward: Callable, params, data) -> Tuple[Array, Array]:
    """(val, test) accuracy of ``params`` on the full graph — the per-round
    evaluation every training backend runs."""
    logits = forward(params, data, data["nbr_mask"])
    return (
        masked_accuracy(logits, data["labels"], data["val_mask"]),
        masked_accuracy(logits, data["labels"], data["test_mask"]),
    )


def make_evaluate(forward: Callable) -> Callable:
    """:func:`accuracies` jitted as the program ``evaluate``."""

    @jax.jit
    def evaluate(params, data):
        return accuracies(forward, params, data)

    return evaluate


def make_local_update(loss_fn: Callable, cfg: FederatedConfig) -> Callable:
    """One client's local phase: ``cfg.local_steps`` Adam steps from the
    global params (with optional FedProx pull). Shared verbatim by the vmap
    and shard_map backends so their trajectories match.

    When ``cfg.privacy`` enables DP, the client's update delta is clipped
    and noised (privacy/dp.py) before it leaves the local phase — both
    backends pass the same per-(round, client) ``noise_key``, so the
    privatised trajectories match too. With DP off, ``noise_key`` is dead
    and the computation is bit-identical to the privacy-free trainer.
    """
    priv = cfg.privacy
    dp = (
        make_dp_transform(priv, num_selected(cfg)) if priv.dp_enabled else None
    )

    def local_update(gparams, opt_state, data, nb_mask, tr_mask, noise_key):
        def one(carry, _):
            params, opt = carry
            grads = jax.grad(loss_fn)(params, data, nb_mask, tr_mask)
            with jax.named_scope("adam"):
                if cfg.aggregator == "fedprox":
                    grads = fedprox_grad(params, gparams, grads, cfg.prox_mu)
                params, opt = adam_update(
                    grads, opt, params, cfg.lr, weight_decay=cfg.weight_decay
                )
            return (params, opt), None

        (params, opt_state), _ = jax.lax.scan(
            one, (gparams, opt_state), None, length=cfg.local_steps
        )
        if dp is not None:
            params = dp(noise_key, gparams, params)
        return params, opt_state

    return local_update


def num_selected(cfg: FederatedConfig) -> int:
    """Participants per round under Algorithm 2's CS(t), in [1, K].

    Half-up rounding (floor(x + 0.5)), NOT Python's banker's rounding:
    ``round`` resolves .5 boundaries to the even neighbour, so
    client_fraction=0.5 with K=5 silently trained 2 clients instead of 3
    and n_sel jumped non-monotonically along fraction sweeps. Half-up is
    monotone in the fraction, and the result is clamped to K so a fraction
    marginally above 1.0 cannot schedule a phantom client.
    """
    n = int(math.floor(cfg.client_fraction * cfg.num_clients + 0.5))
    return min(cfg.num_clients, max(1, n))


def selection_schedule(cfg: FederatedConfig) -> Tuple[np.ndarray, np.ndarray]:
    """Algorithm 2's CS(t), precomputed host-side for the whole run.

    Returns ``(sel, chosen)``:
      sel    — (rounds, K) float32 0/1 participation weights, the layout the
               shard_map backend scans over (each shard reads its column);
      chosen — (rounds, n_sel) int32 indices of the participating clients,
               the layout the vmap backend gathers with.

    Both backends consume the SAME schedule (same RNG stream), so partial
    participation cannot make their trajectories diverge.
    """
    K = cfg.num_clients
    n_sel = num_selected(cfg)
    if n_sel >= K:
        sel = np.ones((cfg.rounds, K), np.float32)
        chosen = np.broadcast_to(np.arange(K, dtype=np.int32), (cfg.rounds, K))
        return sel, np.ascontiguousarray(chosen)
    rng = np.random.default_rng(cfg.seed + 1)
    sel = np.zeros((cfg.rounds, K), np.float32)
    chosen = np.zeros((cfg.rounds, n_sel), np.int32)
    for t in range(cfg.rounds):
        c = rng.choice(K, size=n_sel, replace=False)
        sel[t, c] = 1.0
        chosen[t] = c
    return sel, chosen


def best_metrics(val_curve: Sequence[float], test_curve: Sequence[float]) -> Tuple[float, float]:
    """Best-checkpoint rule shared by every runner: the FIRST round that
    attains the maximum validation accuracy reports its test accuracy."""
    if not len(val_curve):
        return 0.0, 0.0
    i = int(np.argmax(np.asarray(val_curve)))
    return float(val_curve[i]), float(test_curve[i])


def comm_report(cfg: FederatedConfig, g: Graph, part: Partition):
    """Pre-training communication accounting (Theorem 1 / Appendix F)."""
    if cfg.method != "fedgat":
        return None
    fn = comm_mod.comm_cost_for_engine(cfg.model.engine)
    return fn(g, part, num_layers=cfg.model.num_layers) if fn is not None else None


def mesh_description(mesh) -> Optional[Dict[str, Any]]:
    """Serializable stand-in for a live ``Mesh`` in result dicts (results
    must pickle/JSON cleanly for the benchmark dumps)."""
    if mesh is None:
        return None
    return {
        "axis_names": [str(n) for n in mesh.axis_names],
        "axis_sizes": [int(s) for s in mesh.devices.shape],
        "num_devices": int(mesh.devices.size),
        "num_processes": len({d.process_index for d in mesh.devices.flat}),
        "platform": str(mesh.devices.flat[0].platform),
    }


def build_result(
    *,
    cfg: FederatedConfig,
    params: Any,
    val_curve: List[float],
    test_curve: List[float],
    part: Partition,
    g: Graph,
    seconds: float,
    mesh=None,
    cohort: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """The one result schema both backends return.

    ``cohort`` is the cohort scheduler's report (mode, lanes, churn
    accounting) when the run was cohort-streamed, else None — the key is
    present either way so the schema never varies across paths.
    """
    best_val, best_test = best_metrics(val_curve, test_curve)
    node_influence = (
        node_influence_bound(g) if cfg.privacy.dp_granularity == "node" else None
    )
    privacy = privacy_report(
        cfg.privacy, rounds=cfg.rounds, num_clients=cfg.num_clients,
        num_selected=num_selected(cfg), pack_released=pack_released(cfg),
        node_influence=node_influence,
    )
    comm = comm_report(cfg, g, part)
    if telemetry.enabled():
        telemetry.gauge("federated.rounds").set(float(cfg.rounds))
        telemetry.gauge("federated.seconds").set(float(seconds))
        if privacy["epsilon"] is not None:
            telemetry.gauge("privacy.epsilon").set(float(privacy["epsilon"]))
        if comm is not None:
            telemetry.gauge("comm.upload_scalars").set(float(comm.upload_scalars))
            telemetry.gauge("comm.download_scalars").set(float(comm.download_scalars))
            telemetry.gauge("comm.cross_client_edges").set(float(comm.cross_client_edges))
    return {
        "params": params,
        "val_curve": val_curve,
        "test_curve": test_curve,
        "best_val": best_val,
        "best_test": best_test,
        "final_test": test_curve[-1] if test_curve else 0.0,
        "comm": comm,
        "partition": part,
        "seconds": seconds,
        "backend": cfg.backend,
        "mesh": mesh_description(mesh),
        "cohort": cohort,
        "epsilon": privacy["epsilon"],
        "privacy": privacy,
        "manifest": build_manifest(cfg=cfg, mesh=mesh_description(mesh)),
    }


# ---------------------------------------------------------------------------
# Trainer: one entry, two backends
# ---------------------------------------------------------------------------

class Trainer:
    """Unified federated trainer; backend selected by ``cfg.backend``."""

    def __init__(self, cfg: FederatedConfig):
        from repro.federated.cohort import AGGREGATION_MODES

        if cfg.backend not in BACKENDS:
            raise ValueError(
                f"unknown backend {cfg.backend!r}: supported backends are {list(BACKENDS)}"
            )
        if not 0.0 < cfg.client_fraction <= 1.0:
            raise ValueError(
                f"client_fraction={cfg.client_fraction} must be in (0, 1]"
            )
        if cfg.aggregation_mode not in AGGREGATION_MODES:
            raise ValueError(
                f"unknown aggregation_mode {cfg.aggregation_mode!r}: "
                f"supported modes are {list(AGGREGATION_MODES)}"
            )
        if cfg.max_concurrent_clients is not None:
            if cfg.max_concurrent_clients < 1:
                raise ValueError(
                    f"max_concurrent_clients={cfg.max_concurrent_clients} must be >= 1"
                )
            if cfg.max_concurrent_clients > cfg.num_clients:
                raise ValueError(
                    f"max_concurrent_clients={cfg.max_concurrent_clients} exceeds "
                    f"num_clients={cfg.num_clients}: a cohort cannot be larger "
                    "than the client population"
                )
        if not 0.0 <= cfg.churn_drop_rate < 1.0 or not 0.0 <= cfg.churn_join_rate < 1.0:
            raise ValueError("churn rates must be in [0, 1)")
        if (cfg.churn_drop_rate > 0 or cfg.churn_join_rate > 0):
            if cfg.aggregation_mode != "buffered":
                raise ValueError(
                    "mid-round churn (churn_drop_rate / churn_join_rate) "
                    "requires aggregation_mode='buffered'"
                )
            if cfg.privacy.noise_multiplier > 0:
                raise ValueError(
                    "mid-round churn with DP noise is not supported: the "
                    "noise std and the RDP accountant are calibrated to the "
                    "CS(t) participant count, which churn perturbs — disable "
                    "churn or set noise_multiplier=0"
                )
        cfg.privacy.validate()
        if cfg.privacy.secure_agg_protocol and cfg.churn_join_rate > 0:
            raise ValueError(
                "secure_agg_mode='protocol' runs key agreement over the "
                "round's advertised CS(t) cohort, so clients joining "
                "mid-round (churn_join_rate > 0) have no pairwise keys — "
                "use secure_agg_mode='pairwise' or disable join churn "
                "(drop churn is supported: dropped clients' masks are "
                "recovered from secret shares)"
            )
        if cfg.privacy.pack_noise_multiplier > 0 and not pack_released(cfg):
            raise ValueError(
                f"pack_noise_multiplier > 0 but method {cfg.method!r} with "
                f"engine {method_model_config(cfg).engine!r} never releases "
                "a pack — there is nothing to noise (use a pack-based "
                "engine like 'matrix'/'vector', or drop the knob)"
            )
        self.cfg = cfg

    def run(self, g: Graph, mesh=None) -> Dict[str, Any]:
        if self.cfg.backend == "shard_map":
            from repro.federated.sharded import _run_shard_map  # lazy: avoid cycle

            return _run_shard_map(g, self.cfg, mesh)
        if mesh is not None:
            raise ValueError(
                f"mesh given but backend is {self.cfg.backend!r}; "
                "use backend='shard_map' to run on a mesh"
            )
        return self._run_vmap(g)

    def _run_vmap(self, g: Graph) -> Dict[str, Any]:
        """Paper Algorithm 2: rounds of local training + aggregation."""
        cfg = self.cfg
        from repro.federated.cohort import cohort_active, run_cohort_rounds

        if cohort_active(cfg):
            # Cohort streaming: same schedule, same privacy streams, lanes
            # bounded by max_concurrent_clients instead of n_sel.
            return run_cohort_rounds(g, cfg, backend="vmap")
        key = jax.random.PRNGKey(cfg.seed)
        k_pack, k_init = jax.random.split(key)

        part = dirichlet_partition(g.labels, cfg.num_clients, cfg.beta, cfg.seed)
        K = cfg.num_clients

        nb_masks, tr_masks = client_masks(cfg, g, part)
        init_fn, forward, data = build_forward(cfg, g, k_pack)
        global_params = init_fn(k_init)

        local_update = make_local_update(make_loss_fn(forward), cfg)
        priv = cfg.privacy
        noise_base = noise_base_key(cfg.seed)
        mask_base = mask_base_key(cfg.seed)

        @jax.jit
        def round_step(gparams, opt_states, server_state, data, nb_masks,
                       tr_masks, chosen, sel_row, t):
            """chosen: (n_sel,) int — the clients CS(t) picked this round;
            sel_row: (K,) its 0/1 weight layout; t: round index (traced so
            every round shares one trace).

            Only the selected clients are gathered and updated — unselected
            clients run no compute at all and keep their optimizer state
            (the pre-gather layout wasted K/n_sel of the local-update work
            on clients whose params were then zero-weighted away).
            """
            sel_opt = jax.tree.map(
                lambda x: jnp.take(x, chosen, axis=0), opt_states
            )
            noise_keys = jax.vmap(lambda c: client_round_key(noise_base, t, c))(chosen)
            stacked_params, sel_opt = jax.vmap(
                local_update, in_axes=(None, 0, None, 0, 0, 0)
            )(
                gparams, sel_opt, data,
                jnp.take(nb_masks, chosen, axis=0),
                jnp.take(tr_masks, chosen, axis=0),
                noise_keys,
            )
            if priv.secure_agg:
                # Each selected client ships a masked update; the pairwise
                # masks cancel in the fedavg mean below (secure_agg.py).
                with jax.named_scope("fold"):
                    stacked_params = jax.vmap(
                        lambda p, c: add_client_mask(
                            mask_base, t, c, sel_row, p, priv.mask_scale
                        )
                    )(stacked_params, chosen)
            opt_states = jax.tree.map(
                lambda full, new: full.at[chosen].set(new), opt_states, sel_opt
            )
            with jax.named_scope("fold"):
                if cfg.aggregator == "fedadam":
                    new_global, server_state = fedadam_server(
                        gparams, stacked_params, server_state, cfg.server_lr
                    )
                else:
                    new_global = fedavg(stacked_params)
            return new_global, opt_states, server_state

        evaluate = make_evaluate(forward)
        opt_states = jax.vmap(lambda _: adam_init(global_params))(jnp.arange(K))
        server_state = adam_init(global_params)

        val_curve, test_curve = [], []
        t0 = time.time()
        sel_sched, chosen_sched = selection_schedule(cfg)
        traced = telemetry.enabled()
        q = num_selected(cfg) / cfg.num_clients
        for t in range(cfg.rounds):
            with telemetry.span("round", round=t, backend="vmap"):
                with telemetry.span("step", selected=int(sel_sched[t].sum())):
                    global_params, opt_states, server_state = round_step(
                        global_params, opt_states, server_state, data,
                        nb_masks, tr_masks,
                        jnp.asarray(chosen_sched[t]),
                        jnp.asarray(sel_sched[t]),
                        jnp.asarray(t, jnp.int32),
                    )
                with telemetry.span("evaluate"):
                    va, ta = evaluate(global_params, data)
            val_curve.append(float(va))
            test_curve.append(float(ta))
            if traced and priv.dp_enabled:
                # Host-side ε trajectory: recomputed per round from the
                # accountant; never touches the jitted computation.
                from repro.privacy import compute_epsilon

                telemetry.gauge("privacy.epsilon").set(
                    compute_epsilon(priv.noise_multiplier, t + 1, q, priv.delta)
                )
                telemetry.event(
                    "privacy.round", round=t,
                    epsilon=telemetry.gauge("privacy.epsilon").value,
                )

        return build_result(
            cfg=cfg, params=global_params, val_curve=val_curve,
            test_curve=test_curve, part=part, g=g, seconds=time.time() - t0,
        )


def run_federated(
    g: Graph,
    cfg: FederatedConfig,
    *,
    backend: Optional[str] = None,
    mesh=None,
) -> Dict[str, Any]:
    """Run federated training; ``backend`` overrides ``cfg.backend``."""
    if backend is not None:
        cfg = replace(cfg, backend=backend)
    return Trainer(cfg).run(g, mesh=mesh)


# ---------------------------------------------------------------------------
# Centralised baselines
# ---------------------------------------------------------------------------

def train_centralized(
    g: Graph,
    model: str = "gat",
    steps: int = 200,
    lr: float = 0.01,
    weight_decay: float = 1e-3,
    seed: int = 0,
    mcfg: Optional[FedGATConfig] = None,
    gcn_hidden: int = 16,
) -> Dict[str, Any]:
    """Centralised GAT / GCN / FedGAT-approximation baselines (Table 1)."""
    h = jnp.asarray(g.features)
    labels = jnp.asarray(g.labels)
    key = jax.random.PRNGKey(seed)
    k_pack, k_init = jax.random.split(key)

    if model == "gcn":
        nbr_idx = jnp.asarray(g.nbr_idx)
        coef = jnp.asarray(normalized_nbr_coeffs(g.nbr_idx, g.nbr_mask))
        params = init_gcn_params(k_init, g.feature_dim, gcn_hidden, g.num_classes)

        def forward(p):
            return gcn_forward_nbr(p, h, nbr_idx, coef)
    else:
        mcfg = mcfg or FedGATConfig(engine="exact" if model == "gat" else "direct")
        net = FedGAT(mcfg)
        net.precommunicate(k_pack, g)
        params = net.init(k_init, g)

        def forward(p):
            return net.apply(p, g)

    train_mask = jnp.asarray(g.train_mask)
    val_mask = jnp.asarray(g.val_mask)
    test_mask = jnp.asarray(g.test_mask)

    def loss_fn(p):
        return masked_cross_entropy(forward(p), labels, train_mask)

    @jax.jit
    def step_fn(p, opt):
        grads = jax.grad(loss_fn)(p)
        return adam_update(grads, opt, p, lr, weight_decay=weight_decay)

    @jax.jit
    def evaluate(p):
        logits = forward(p)
        return (
            masked_accuracy(logits, labels, val_mask),
            masked_accuracy(logits, labels, test_mask),
        )

    opt = adam_init(params)
    val_curve, test_curve = [], []
    for _ in range(steps):
        params, opt = step_fn(params, opt)
        va, ta = evaluate(params)
        val_curve.append(float(va))
        test_curve.append(float(ta))
    best_val, best_test = best_metrics(val_curve, test_curve)
    return {
        "params": params,
        "best_val": best_val,
        "best_test": best_test,
        "final_test": test_curve[-1],
        "val_curve": val_curve,
        "test_curve": test_curve,
    }
