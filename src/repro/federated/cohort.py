"""Cohort-streaming federated rounds: clients decoupled from devices.

Both Trainer backends historically bound one execution lane to one client
(a vmap lane, or a mesh shard), capping the population K at the host's
lane budget. This module inserts a scheduling layer between Algorithm 2's
CS(t) selection and the backends: a round's selected clients are split
into *cohorts* of at most ``FederatedConfig.max_concurrent_clients``
clients, and every cohort is streamed through ONE jitted local-update step
whose lane count equals the cohort size. The round aggregate is carried as
a :class:`~repro.federated.aggregation.RunningAggregate` (weighted sum +
weight total), so round memory is O(cohort), never O(K) — K=1024 clients
train on 8 forced host devices.

The streamed schedule is *the same schedule*: per-(round, client) DP noise
keys and pairwise secure-aggregation masks are derived from the client's
global id exactly as the one-lane-per-client paths derive them, so the
noise streams are bit-identical and the pairwise masks still cancel when
the last cohort's sum lands — cohort boundaries are invisible to the
privacy stack, and sync-mode metrics stay in lockstep (<= 1e-6, float
re-association only) with the legacy paths.

Two aggregation modes (``FederatedConfig.aggregation_mode``):

  sync     — the server barriers on all cohorts; the finished running mean
             is exactly the round's FedAvg/FedAdam aggregate.
  buffered — cohorts are treated as concurrently dispatched at round start
             and applied as they land: cohort c's contribution is
             discounted by the polynomial staleness weight
             λ(c) = (1 + c)^(-staleness_power) (FedAsync/FedBuff style),
             and mid-round churn is tolerated — selected clients may drop
             and unselected clients may join (``churn_drop_rate`` /
             ``churn_join_rate``), with secure-aggregation masks keyed on
             the round's *actual* participation row so they still cancel.
             With ``staleness_power=0`` and no churn, buffered mode
             coincides with sync mode exactly.

Backends differ only in how one cohort maps onto compute:

  vmap      — cohort lanes are vmap lanes on the default device;
  shard_map — cohort lanes are mesh shards, one device per lane (the mesh
              covers the *devices*, not the clients), with the cohort's
              weighted sum reduced by a single ``lax.psum``.

Per-cohort inputs (neighbour/train masks) are staged host-side for the
active cohort only (:func:`~repro.federated.partition.stage_cohort_masks`)
and memoised, so peak staging memory is O(lanes · N · B) regardless of K.
"""
from __future__ import annotations

import time
from collections import OrderedDict
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.federated.aggregation import (
    RunningAggregate,
    fedadam_update,
    running_update,
)
from repro.federated.partition import (
    Partition,
    dirichlet_partition,
    stage_cohort_masks,
)
from repro.graphs.graph import Graph
from repro.optim.adamw import adam_init
from repro.privacy import (
    DropoutRecoveryError,
    SecureAggRound,
    add_client_mask,
    client_round_key,
    flatten_pytree,
    mask_base_key,
    noise_base_key,
)

Array = jax.Array

AGGREGATION_MODES = ("sync", "buffered")

# Dedicated host-side RNG stream for buffered-mode churn: sync runs never
# draw from it, so enabling/disabling churn cannot perturb CS(t) or the
# privacy streams.
_CHURN_STREAM = 0xC0C0


def cohort_active(cfg) -> bool:
    """True when the run goes through the cohort scheduler: the cohort
    size knob is set, buffered aggregation was requested, or the real
    secure-aggregation protocol is on (its key agreement and finite-field
    unmasking run host-side, between jitted steps — only this driver has
    a host hop per cohort)."""
    return (
        cfg.max_concurrent_clients is not None
        or cfg.aggregation_mode != "sync"
        or cfg.privacy.secure_agg_protocol
    )


def cohort_lanes(cfg, backend: str, num_devices: Optional[int] = None) -> int:
    """Execution lanes per cohort step.

    ``max_concurrent_clients`` caps it; a cohort never needs more lanes
    than the round has participants; the shard_map backend additionally
    caps at the device count (one lane per device).
    """
    from repro.federated.trainer import num_selected

    lanes = num_selected(cfg)
    if cfg.max_concurrent_clients is not None:
        lanes = min(lanes, cfg.max_concurrent_clients)
    if backend == "shard_map":
        lanes = min(lanes, num_devices if num_devices else len(jax.devices()))
    return max(1, lanes)


# ---------------------------------------------------------------------------
# Host-side round planning (CS(t) -> cohorts, churn, staleness)
# ---------------------------------------------------------------------------

class RoundPlan(NamedTuple):
    """One round's cohort schedule, precomputed host-side."""

    ids: np.ndarray          # (num_cohorts, lanes) int32 client ids; pad = K
    weights: np.ndarray      # (num_cohorts, lanes) float32 1=live, 0=pad/drop
    sel_row: np.ndarray      # (K,) float32 ACTUAL participation (after churn)
    staleness: np.ndarray    # (num_cohorts,) float32 λ per landing cohort
    joined: int              # clients that joined mid-round (buffered churn)
    dropped: int             # selected clients that dropped mid-round


def plan_round(
    cfg,
    chosen_row: np.ndarray,
    lanes: int,
    rng: Optional[np.random.Generator],
) -> RoundPlan:
    """Split one round's CS(t)-selected clients into device-sized cohorts.

    Padding lanes carry the out-of-range id K with weight 0: their gathers
    clip to a real client (finite compute), their aggregate contribution is
    exactly zero, and their optimizer-state scatters drop.
    """
    K = cfg.num_clients
    participants = [int(c) for c in np.asarray(chosen_row).reshape(-1)]
    joined = dropped = 0
    if cfg.aggregation_mode == "buffered" and rng is not None and (
        cfg.churn_drop_rate > 0 or cfg.churn_join_rate > 0
    ):
        keep = rng.random(len(participants)) >= cfg.churn_drop_rate
        if not keep.any():                      # a round never goes empty
            keep[int(rng.integers(len(participants)))] = True
        dropped = int((~keep).sum())
        participants = [p for p, k in zip(participants, keep) if k]
        others = np.setdiff1d(np.arange(K), np.asarray(chosen_row))
        if others.size and cfg.churn_join_rate > 0:
            join = others[rng.random(others.size) < cfg.churn_join_rate]
            joined = int(join.size)
            participants.extend(int(j) for j in join)
    sel_row = np.zeros(K, np.float32)
    sel_row[participants] = 1.0
    n_cohorts = -(-len(participants) // lanes)
    ids = np.full((n_cohorts, lanes), K, np.int32)
    weights = np.zeros((n_cohorts, lanes), np.float32)
    for c in range(n_cohorts):
        chunk = participants[c * lanes : (c + 1) * lanes]
        ids[c, : len(chunk)] = chunk
        weights[c, : len(chunk)] = 1.0
    if cfg.aggregation_mode == "buffered":
        lam = (1.0 + np.arange(n_cohorts, dtype=np.float32)) ** (
            -float(cfg.staleness_power)
        )
    else:
        lam = np.ones(n_cohorts, np.float32)
    return RoundPlan(
        ids=ids, weights=weights, sel_row=sel_row, staleness=lam,
        joined=joined, dropped=dropped,
    )


def plan_rounds(cfg, chosen_sched: np.ndarray, lanes: int) -> List[RoundPlan]:
    """Every round's cohort plan (churn RNG advanced round by round)."""
    rng = None
    if cfg.aggregation_mode == "buffered" and (
        cfg.churn_drop_rate > 0 or cfg.churn_join_rate > 0
    ):
        rng = np.random.default_rng(cfg.seed + _CHURN_STREAM)
    return [plan_round(cfg, chosen_sched[t], lanes, rng) for t in range(cfg.rounds)]


class _CohortStager:
    """Memoised per-cohort mask staging: stacks ONLY the active cohort's
    client masks (O(lanes · N · B)), with an LRU memo sized for repeating
    cohort compositions (client_fraction == 1 repeats every round)."""

    def __init__(self, g: Graph, part: Partition, lanes: int,
                 per_client_nb: bool, capacity: int = 32):
        self.g, self.part, self.lanes = g, part, lanes
        self.per_client_nb = per_client_nb
        self.capacity = max(capacity, 2)
        self._memo: "OrderedDict[tuple, tuple]" = OrderedDict()

    def __call__(self, live_ids: Sequence[int]):
        key = tuple(int(i) for i in live_ids)
        hit = self._memo.get(key)
        if hit is not None:
            self._memo.move_to_end(key)
            return hit
        nb, tr = stage_cohort_masks(
            self.g, self.part, key, self.lanes, neighbor=self.per_client_nb
        )
        self._memo[key] = (nb, tr)
        while len(self._memo) > self.capacity:
            self._memo.popitem(last=False)
        return nb, tr


# ---------------------------------------------------------------------------
# The jitted cohort step, one per backend (same signature, same math)
# ---------------------------------------------------------------------------

def make_vmap_cohort_step(cfg, local_update: Callable, K: int) -> Callable:
    """One cohort on vmap lanes.

    (gparams, agg, opt_slice, data, nb, tr, ids, w, lam, sel_row, t)
      -> (agg', new_opt_slice)

    ``data`` is the graph's device arrays (``trainer.build_forward``).
    ``nb`` is stacked (lanes, N, B) for per-client visibility (distgat) or
    a single shared (N, B) mask otherwise (broadcast via in_axes=None, so
    no per-lane copy exists).
    """
    priv = cfg.privacy
    per_client_nb = cfg.method == "distgat"
    noise_base = noise_base_key(cfg.seed)
    mask_base = mask_base_key(cfg.seed)

    @jax.jit
    def step(gparams, agg, opt_slice, data, nb, tr, ids, w, lam, sel_row, t):
        noise_keys = jax.vmap(lambda c: client_round_key(noise_base, t, c))(ids)
        stacked, new_opt = jax.vmap(
            local_update,
            in_axes=(None, 0, None, 0 if per_client_nb else None, 0, 0),
        )(gparams, opt_slice, data, nb, tr, noise_keys)
        with jax.named_scope("fold"):
            if priv.secure_agg:
                stacked = jax.vmap(
                    lambda p, c: add_client_mask(
                        mask_base, t, c, sel_row, p, priv.mask_scale
                    )
                )(stacked, ids)
            agg = running_update(agg, stacked, w, scale=lam)
        return agg, new_opt

    return step


def make_shard_cohort_step(cfg, local_update: Callable, mesh, K: int) -> Callable:
    """One cohort on mesh shards: one device per lane, the cohort's
    weighted sum reduced with a single ``lax.psum`` over the ``lanes``
    axis. Same signature and math as the vmap step."""
    from jax.sharding import PartitionSpec as P

    from repro._compat.jax_compat import shard_map

    priv = cfg.privacy
    per_client_nb = cfg.method == "distgat"
    noise_base = noise_base_key(cfg.seed)
    mask_base = mask_base_key(cfg.seed)

    def body(gparams, agg, opt_slice, data, nb, tr, ids, w, lam, sel_row, t):
        cid = ids[0]
        wl = w[0]
        opt1 = jax.tree.map(lambda x: x[0], opt_slice)
        nbm = nb[0] if per_client_nb else nb
        noise_key = client_round_key(noise_base, t, cid)
        params, new_opt = local_update(gparams, opt1, data, nbm, tr[0], noise_key)
        with jax.named_scope("fold"):
            if priv.secure_agg:
                params = add_client_mask(
                    mask_base, t, cid, sel_row, params, priv.mask_scale
                )
            cohort_sum = jax.tree.map(
                lambda x: jax.lax.psum(wl.astype(x.dtype) * x, "lanes"), params
            )
            wsum = jax.lax.psum(wl, "lanes")
            agg = RunningAggregate(
                sum=jax.tree.map(
                    lambda a, s: a + lam.astype(a.dtype) * s, agg.sum, cohort_sum
                ),
                weight=agg.weight + lam * wsum,
            )
        return agg, jax.tree.map(lambda x: x[None], new_opt)

    lanes = P("lanes")
    return jax.jit(
        shard_map(
            body,
            mesh=mesh,
            in_specs=(P(), P(), lanes, P(), lanes if per_client_nb else P(),
                      lanes, lanes, lanes, P(), P(), P()),
            out_specs=(P(), lanes),
        )
    )


def make_vmap_collect_step(cfg, local_update: Callable, K: int) -> Callable:
    """One cohort on vmap lanes, returning RAW per-lane updated params.

    The secure-agg protocol path: no in-jit masks and no in-jit fold —
    masking and aggregation happen host-side in the finite field
    (privacy/secure_agg.py), so the step only runs the local updates.
    """
    per_client_nb = cfg.method == "distgat"
    noise_base = noise_base_key(cfg.seed)

    @jax.jit
    def step(gparams, opt_slice, data, nb, tr, ids, t):
        noise_keys = jax.vmap(lambda c: client_round_key(noise_base, t, c))(ids)
        return jax.vmap(
            local_update,
            in_axes=(None, 0, None, 0 if per_client_nb else None, 0, 0),
        )(gparams, opt_slice, data, nb, tr, noise_keys)

    return step


def make_shard_collect_step(cfg, local_update: Callable, mesh, K: int) -> Callable:
    """Shard_map twin of :func:`make_vmap_collect_step`: one device per
    lane, per-lane params returned WITHOUT any cross-lane collective —
    the field aggregation is host-side and associative, so no psum is
    needed (or wanted: the server must only ever see masked payloads)."""
    from jax.sharding import PartitionSpec as P

    from repro._compat.jax_compat import shard_map

    per_client_nb = cfg.method == "distgat"
    noise_base = noise_base_key(cfg.seed)

    def body(gparams, opt_slice, data, nb, tr, ids, t):
        cid = ids[0]
        opt1 = jax.tree.map(lambda x: x[0], opt_slice)
        nbm = nb[0] if per_client_nb else nb
        noise_key = client_round_key(noise_base, t, cid)
        params, new_opt = local_update(gparams, opt1, data, nbm, tr[0], noise_key)
        return (
            jax.tree.map(lambda x: x[None], params),
            jax.tree.map(lambda x: x[None], new_opt),
        )

    lanes = P("lanes")
    return jax.jit(
        shard_map(
            body,
            mesh=mesh,
            in_specs=(P(), lanes, P(), lanes if per_client_nb else P(),
                      lanes, lanes, P()),
            out_specs=(lanes, lanes),
        )
    )


def _lanes_mesh(lanes: int):
    """A mesh of ``lanes`` devices (axis "lanes") — over DEVICES, not
    clients: the cohort scheduler owns the client dimension."""
    from jax.sharding import Mesh

    devs = jax.devices()
    if len(devs) < lanes:
        raise ValueError(
            f"cohort of {lanes} lanes needs >= {lanes} devices, have "
            f"{len(devs)} (set XLA_FLAGS=--xla_force_host_platform_device_count"
            f"=... or lower max_concurrent_clients)"
        )
    return Mesh(np.array(devs[:lanes]), ("lanes",))


# ---------------------------------------------------------------------------
# The streaming round driver (shared by both backends)
# ---------------------------------------------------------------------------

def _finalize_protocol_round(
    sar: SecureAggRound,
    cfg,
    t: int,
    dim: int,
    priv,
    lam_by: Dict[int, float],
    vec_by: Dict[int, np.ndarray],
    gvec: np.ndarray,
    unflatten: Callable,
):
    """Server side of the round: unmask, recover dropouts, decode the mean.

    When seed reconstruction is impossible (survivors below the Shamir
    threshold) the round degrades: the failure is counted and the protocol
    re-runs among the survivors under a fresh ``attempt`` index — in this
    simulation the clients' deltas are still in hand, so the re-run is a
    re-mask + re-sum rather than a re-train, exactly as the real protocol's
    retry round would be.
    """
    survivors = sorted(lam_by)
    try:
        total, info = sar.finalize(survivors)
        if info["dropped"]:
            telemetry.counter("privacy.secure_agg.recovered_seeds").inc(
                info["recovered_seeds"]
            )
            telemetry.event(
                "privacy.secure_agg.recovered", round=t, dropped=info["dropped"]
            )
    except DropoutRecoveryError as exc:
        telemetry.counter("privacy.secure_agg.recovery_failures").inc()
        telemetry.event("privacy.secure_agg.degraded", round=t, reason=str(exc))
        retry = SecureAggRound(
            cfg.seed, t, survivors, dim,
            quant_bits=priv.quant_bits, quant_range=priv.quant_range,
            threshold=None, attempt=1,
        )
        for cid in survivors:
            retry.accumulate(cid, retry.client_payload(cid, vec_by[cid]))
        total, info = retry.finalize(survivors)
    if info["saturated"]:
        telemetry.counter("privacy.secure_agg.saturated_elements").inc(
            info["saturated"]
        )
    wsum = sum(lam_by.values())
    return unflatten(gvec + total / wsum)


def run_cohort_rounds(g: Graph, cfg, backend: str, mesh=None) -> Dict[str, Any]:
    """Cohort-streamed realisation of paper Algorithm 2 for either backend.

    Between jitted cohort steps, all carried state (global params, the
    per-client optimizer bank, the running aggregate) lives host-side as
    numpy pytrees: host arrays are uncommitted, so the SAME driver feeds a
    default-device vmap step or a mesh-sharded shard_map step without any
    cross-committed-device friction.
    """
    from repro.federated.trainer import (
        build_forward,
        build_result,
        make_evaluate,
        make_local_update,
        make_loss_fn,
        num_selected,
        selection_schedule,
    )

    K = cfg.num_clients
    t0 = time.time()
    key = jax.random.PRNGKey(cfg.seed)
    k_pack, k_init = jax.random.split(key)
    part = dirichlet_partition(g.labels, K, cfg.beta, cfg.seed)

    init_fn, forward, data = build_forward(cfg, g, k_pack)
    global_params = jax.device_get(init_fn(k_init))

    cohort_report: Dict[str, Any] = {
        "mode": cfg.aggregation_mode,
        "max_concurrent_clients": cfg.max_concurrent_clients,
        "staleness_power": (
            float(cfg.staleness_power)
            if cfg.aggregation_mode == "buffered" else 0.0
        ),
        "joined": 0,
        "dropped": 0,
    }

    if cfg.rounds == 0:
        # Pure setup/accounting: no devices, no mesh needed.
        cohort_report.update(lanes=0, cohorts_per_round=0)
        return build_result(
            cfg=cfg, params=global_params, val_curve=[], test_curve=[],
            part=part, g=g, seconds=time.time() - t0, mesh=mesh,
            cohort=cohort_report,
        )

    if backend == "shard_map":
        if jax.process_count() > 1:
            raise NotImplementedError(
                "cohort streaming runs on a single-process mesh; multi-"
                "process runs keep the one-client-per-shard layout (unset "
                "max_concurrent_clients / use aggregation_mode='sync', and "
                "with secure aggregation use secure_agg_mode='pairwise' — "
                "the in-jit masks that cancel in the cross-process psum)"
            )
        if mesh is not None:
            lanes = int(mesh.devices.size)
        else:
            lanes = cohort_lanes(cfg, backend)
            mesh = _lanes_mesh(lanes)
    else:
        if mesh is not None:
            raise ValueError("mesh given but backend is 'vmap'")
        lanes = cohort_lanes(cfg, backend)

    if backend == "shard_map":
        # Replicated on the lanes mesh once, not re-sent every cohort step.
        from jax.sharding import NamedSharding, PartitionSpec as P

        data = jax.device_put(data, NamedSharding(mesh, P()))
    protocol = cfg.privacy.secure_agg_protocol
    local_update = make_local_update(make_loss_fn(forward), cfg)
    if backend == "shard_map":
        step = (
            make_shard_collect_step(cfg, local_update, mesh, K)
            if protocol
            else make_shard_cohort_step(cfg, local_update, mesh, K)
        )
    else:
        step = (
            make_vmap_collect_step(cfg, local_update, K)
            if protocol
            else make_vmap_cohort_step(cfg, local_update, K)
        )

    evaluate = make_evaluate(forward)

    @jax.jit
    def server_apply(gp, mean, srv):
        return fedadam_update(gp, mean, srv, cfg.server_lr)

    # Per-client optimizer bank: (K, ...) host numpy (zeros, matching the
    # legacy backends' stacked adam_init), scatter-updated cohort by cohort.
    adam0 = jax.device_get(adam_init(global_params))
    opt_bank = jax.tree.map(
        lambda x: np.repeat(np.asarray(x)[None], K, axis=0), adam0
    )
    server_state = adam_init(global_params)

    sel_sched, chosen_sched = selection_schedule(cfg)
    plans = plan_rounds(cfg, chosen_sched, lanes)
    cohort_report["lanes"] = lanes
    cohort_report["cohorts_per_round"] = max(p.ids.shape[0] for p in plans)
    cohort_report["joined"] = sum(p.joined for p in plans)
    cohort_report["dropped"] = sum(p.dropped for p in plans)
    # Churn accounting in the process-wide registry (always on — these are
    # the same kind of ad hoc counters the pack cache keeps).
    telemetry.counter("federated.cohort.joined").inc(cohort_report["joined"])
    telemetry.counter("federated.cohort.dropped").inc(cohort_report["dropped"])

    stager = _CohortStager(
        g, part, lanes, per_client_nb=cfg.method == "distgat",
        capacity=max(8, 2 * plans[0].ids.shape[0]),
    )
    shared_nb = data["nbr_mask"]

    val_curve: List[float] = []
    test_curve: List[float] = []
    traced = telemetry.enabled()
    priv = cfg.privacy
    q = num_selected(cfg) / K
    if protocol:
        gvec0, unflatten = flatten_pytree(global_params)
        dim = int(gvec0.size)
    for t in range(cfg.rounds):
        plan = plans[t]
        agg: Any = RunningAggregate(
            sum=jax.tree.map(np.zeros_like, global_params),
            weight=np.zeros((), np.float32),
        )
        g_round = global_params          # every cohort dispatches from here
        if protocol:
            # Key agreement + secret sharing over the ADVERTISED cohort —
            # the pre-churn CS(t) selection: clients that later drop are
            # exactly the ones whose masks the recovery phase removes.
            advertised = sorted(
                {int(c) for c in np.asarray(chosen_sched[t]).reshape(-1)}
            )
            sar = SecureAggRound(
                cfg.seed, t, advertised, dim,
                quant_bits=priv.quant_bits, quant_range=priv.quant_range,
                threshold=priv.secure_agg_threshold,
            )
            gvec = flatten_pytree(g_round)[0]
            lam_by: Dict[int, float] = {}
            vec_by: Dict[int, np.ndarray] = {}
        t_arr = jnp.asarray(t, jnp.int32)
        with telemetry.span(
            "round", round=t, backend=backend, cohorts=int(plan.ids.shape[0])
        ):
            for c in range(plan.ids.shape[0]):
                ids = plan.ids[c]
                w = plan.weights[c]
                with telemetry.span("cohort", cohort=c, live=int((w > 0).sum())):
                    live = ids[w > 0]
                    with telemetry.span("staging"):
                        nb, tr = stager(live)
                        opt_slice = jax.tree.map(
                            lambda x: x[np.minimum(ids, K - 1)], opt_bank
                        )
                    if protocol:
                        with telemetry.span("step"):
                            stacked, new_opt = step(
                                g_round, opt_slice, data,
                                nb if nb is not None else shared_nb, tr,
                                ids, t_arr,
                            )
                        with telemetry.span("host_transfer"):
                            stacked = jax.device_get(stacked)
                            new_opt = jax.device_get(new_opt)
                        # Client side of the protocol: each live lane's
                        # λ-scaled delta is quantized, masked, and only the
                        # masked field payload reaches the running sum.
                        lam_c = float(plan.staleness[c])
                        leaves = jax.tree.leaves(stacked)
                        with telemetry.span("secure_agg_mask"):
                            for lane in np.nonzero(w > 0)[0]:
                                cid = int(ids[lane])
                                cvec = np.concatenate(
                                    [
                                        np.asarray(x[lane], np.float64).ravel()
                                        for x in leaves
                                    ]
                                )
                                delta = lam_c * (cvec - gvec)
                                sar.accumulate(cid, sar.client_payload(cid, delta))
                                lam_by[cid] = lam_c
                                vec_by[cid] = delta
                    else:
                        with telemetry.span("step"):
                            agg, new_opt = step(
                                g_round, agg, opt_slice, data,
                                nb if nb is not None else shared_nb, tr,
                                ids, w, jnp.asarray(plan.staleness[c], jnp.float32),
                                plan.sel_row, t_arr,
                            )
                        with telemetry.span("host_transfer"):
                            new_opt = jax.device_get(new_opt)
                    live_lane = w > 0

                    def scatter(bank, new):
                        bank[ids[live_lane]] = new[live_lane]
                        return bank

                    with telemetry.span("aggregation_fold"):
                        opt_bank = jax.tree.map(scatter, opt_bank, new_opt)
            with telemetry.span("aggregate"):
                if protocol:
                    mean = _finalize_protocol_round(
                        sar, cfg, t, dim, priv, lam_by, vec_by, gvec, unflatten
                    )
                else:
                    agg = jax.device_get(agg)
                    mean = jax.tree.map(
                        lambda s: (s / agg.weight).astype(s.dtype), agg.sum
                    )
                if cfg.aggregator == "fedadam":
                    new_gp, server_state = server_apply(g_round, mean, server_state)
                    global_params = jax.device_get(new_gp)
                else:
                    global_params = jax.device_get(mean) if protocol else mean
            with telemetry.span("evaluate"):
                va, ta = evaluate(global_params, data)
        val_curve.append(float(va))
        test_curve.append(float(ta))
        if traced and priv.dp_enabled:
            # Host-side ε trajectory, same as the legacy vmap loop: the
            # accountant sees CS(t) sampling, not cohort boundaries.
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
        mesh=mesh, cohort=cohort_report,
    )
