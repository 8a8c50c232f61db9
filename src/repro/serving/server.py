"""GraphInferenceServer — online node-classification over a trained FedGAT.

The serving unit of work is one layered forward per (client, graph
version): batched queries are grouped by client, each distinct client costs
one engine forward (through the head-batched ``cheb_attn`` kernel for the
``kernel`` engine), and per-query logits are gathered from it. Packs are cached
per client (:class:`~repro.serving.cache.PackCache`), graph deltas are
absorbed with cheap local pack patches, and the accumulated drift is
tracked against the paper's Thm 3.5 logit bound — a full per-client pack
refresh fires only when the bound is crossed.
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field, replace
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.analysis.error_bounds import thm35_logit_bound
from repro.core.engine import get_engine
from repro.core.fedgat_model import FedGATConfig, layered_forward
from repro.federated.partition import Partition, client_neighbor_masks
from repro.graphs.graph import Graph
from repro.serving.cache import PackCache, PackEntry, graph_fingerprint
from repro.serving.checkpoint import load_bundle
from repro.serving.updates import (
    Coverage,
    GraphDelta,
    apply_delta,
    extend_coverage,
    initial_coverage,
    mass_drift,
    patch_pack,
)

Array = jax.Array

SERVABLE_METHODS = ("fedgat", "distgat")


class Query(NamedTuple):
    client: int
    node: int


class QueryResult(NamedTuple):
    client: int
    node: int
    logits: np.ndarray      # (C,)
    label: int              # argmax class


def client_pack_key(base_key: Array, client: int) -> Array:
    """Deterministic per-client pack key: refreshes rebuild bit-for-bit what
    a from-scratch precommunicate under the same key would."""
    return jax.random.fold_in(base_key, int(client))


@dataclass
class ClientState:
    """Server-side drift bookkeeping for one client's cached pack."""

    covered: Optional[Coverage] = None     # sparse slot set the pack encodes
    b_pack: int = 0                        # pack's padded-degree capacity
    eps: float = 0.0                       # tracked Thm 3.5 score-mass error
    refreshes: int = 0
    patches: int = 0
    history: List[float] = field(default_factory=list)  # eps after each delta


class GraphInferenceServer:
    """Serve node-classification queries from a trained FedGAT checkpoint.

    Typical use::

        server = GraphInferenceServer.from_checkpoint("ckpt/", graph,
                                                      engine="kernel")
        results = server.serve_batch([Query(client=0, node=17), ...])
        server.apply_update(GraphDelta(features=new_h, edges=new_e))
    """

    def __init__(
        self,
        params: Any,
        model_cfg: FedGATConfig,
        graph: Graph,
        *,
        method: str = "fedgat",
        num_clients: int = 1,
        partition: Optional[Partition] = None,
        engine: Optional[str] = None,
        pack_key: Optional[Array] = None,
        refresh_threshold: float = 2.0,
        cache: Optional[PackCache] = None,
        cache_dir: Optional[str] = None,
        privacy: Any = None,
        meta: Optional[Dict[str, Any]] = None,
    ):
        if method not in SERVABLE_METHODS:
            raise ValueError(
                f"method {method!r} is not servable; supported: {SERVABLE_METHODS}"
            )
        if num_clients < 1:
            raise ValueError(f"num_clients must be >= 1, got {num_clients}")
        if refresh_threshold <= 0:
            raise ValueError(f"refresh_threshold must be > 0, got {refresh_threshold}")
        # The engine serves as requested or raises: an unknown name, or a
        # kernel engine whose Pallas stack does not import.
        self.cfg = replace(model_cfg, engine=engine or model_cfg.engine)
        self.engine = get_engine(self.cfg.engine)(self.cfg)
        self.coeffs: Optional[Array] = (
            jnp.asarray(self.cfg.coeffs(), jnp.float32)
            if self.engine.needs_coeffs else None
        )
        self.params = params
        self.method = method
        self.num_clients = int(num_clients)
        self.part = partition
        if method == "distgat":
            if self.part is None:
                raise ValueError(
                    "serving the distgat method needs the training Partition "
                    "(per-client edge visibility); pass partition= or use "
                    "from_checkpoint, which rebuilds it from bundle provenance"
                )
            if self.part.num_clients != self.num_clients:
                raise ValueError(
                    f"partition has {self.part.num_clients} clients, "
                    f"server configured for {self.num_clients}"
                )
        self.pack_key = (
            pack_key if pack_key is not None else jax.random.PRNGKey(0)
        )
        self.refresh_threshold = float(refresh_threshold)
        # cache_dir makes the pack cache survive server restarts: a saved
        # cache there is reloaded (fingerprint-validated), and save_cache()
        # writes back to the same place. Entries reloaded against a changed
        # graph/engine simply miss — the fingerprint is the validity proof.
        self.cache_dir = cache_dir
        if cache is not None:
            self.cache = cache
        elif cache_dir is not None and os.path.exists(
            os.path.join(cache_dir, "cache_index.json")
        ):
            self.cache = PackCache.load(cache_dir)
        else:
            self.cache = PackCache()
        self.privacy = privacy
        self.meta = dict(meta or {})
        self._clients: Dict[int, ClientState] = {}
        self._version = 0
        self._logits_memo: Dict[int, Tuple[int, np.ndarray]] = {}
        self._vis_memo: Dict[int, np.ndarray] = {}
        self._forward = jax.jit(
            lambda p, pack, h, idx, mask: layered_forward(
                self.engine, p, self.coeffs, pack, h, idx, mask
            )
        )
        self._set_graph(graph)

    # -- construction -------------------------------------------------------

    @classmethod
    def from_checkpoint(cls, path: str, graph: Graph, **kwargs) -> "GraphInferenceServer":
        """Load a Trainer bundle (repro.serving.checkpoint) and serve it.

        Method/num_clients/model/privacy provenance come from the bundle;
        for DistGAT checkpoints the training partition is rebuilt from the
        recorded (beta, seed) so per-client edge visibility matches what
        the clients trained under. Keyword overrides win over provenance.
        """
        bundle = load_bundle(path, graph)
        meta = bundle.meta
        method = kwargs.pop("method", meta.get("method", "fedgat"))
        num_clients = kwargs.pop("num_clients", meta.get("num_clients", 1))
        partition = kwargs.pop("partition", None)
        if method == "distgat" and partition is None and "beta" in meta:
            from repro.federated.partition import dirichlet_partition

            partition = dirichlet_partition(
                graph.labels, num_clients, meta["beta"], meta.get("seed", 0)
            )
        return cls(
            bundle.params, bundle.model, graph,
            method=method, num_clients=num_clients, partition=partition,
            privacy=bundle.privacy, meta=meta, **kwargs,
        )

    # -- graph / visibility plumbing ---------------------------------------

    def _set_graph(self, graph: Graph) -> None:
        self.graph = graph
        self._h = jnp.asarray(graph.features)
        self._idx = jnp.asarray(graph.nbr_idx)
        self._mask = jnp.asarray(graph.nbr_mask)
        self._version += 1
        self._logits_memo.clear()
        self._vis_memo.clear()

    def _visible_mask_np(self, client: int) -> np.ndarray:
        """(N, B) bool edge-visibility for ``client`` on the current graph."""
        vis = self._vis_memo.get(client)
        if vis is None:
            if self.method == "distgat":
                vis = client_neighbor_masks(self.graph, self.part, clients=[client])[0]
            else:
                vis = self.graph.nbr_mask
            self._vis_memo[client] = vis
        return vis

    def _fingerprint(self, client: int) -> str:
        # Content-addressed on the CSR arrays: nbr_idx/nbr_mask derive
        # deterministically from (indptr, indices), so hashing the CSR pair
        # covers them at O(E) bytes instead of O(N * B).
        return graph_fingerprint(
            self.graph.features, self.graph.indptr, self.graph.indices,
            self._visible_mask_np(client),
            np.asarray(client_pack_key(self.pack_key, client)),
            extra=(self.cfg.engine, self.cfg.degree, self.cfg.basis,
                   self.cfg.domain, self.cfg.r),
        )

    # -- pack lifecycle -----------------------------------------------------

    def _ensure_client(self, client: int) -> PackEntry:
        """The client's cache entry, building the pack on a miss."""
        if not (0 <= client < self.num_clients):
            raise ValueError(
                f"client {client} out of range [0, {self.num_clients})"
            )
        fp = self._fingerprint(client)
        entry = self.cache.get(client, fp)
        if entry is not None:
            return entry
        vis = self._visible_mask_np(client)
        pack = None
        if self.engine.needs_pack:
            with telemetry.span("serving.pack_build", client=client):
                pack = self.engine.precompute(
                    client_pack_key(self.pack_key, client),
                    self._h, self._idx, jnp.asarray(vis),
                )
        entry = PackEntry(pack=pack, fingerprint=fp)
        self.cache.put(client, entry)
        st = self._clients.setdefault(client, ClientState())
        st.covered = (
            initial_coverage(self.graph, None if self.method != "distgat" else vis)
            if self.engine.needs_pack else None
        )
        st.b_pack = self.graph.max_degree
        st.eps = 0.0
        return entry

    def pack_for(self, client: int) -> Any:
        """The client's current (cached / patched / refreshed) pack."""
        return self._ensure_client(client).pack

    def refresh(self, client: int) -> None:
        """Force a full pack rebuild for ``client`` — bit-identical to a
        from-scratch precommunicate on the current graph under the client's
        deterministic pack key. Resets the tracked drift."""
        self._ensure_client(client)
        st = self._clients[client]
        vis = self._visible_mask_np(client)
        pack = None
        if self.engine.needs_pack:
            pack = self.engine.precompute(
                client_pack_key(self.pack_key, client),
                self._h, self._idx, jnp.asarray(vis),
            )
            st.covered = initial_coverage(
                self.graph, None if self.method != "distgat" else vis
            )
        st.b_pack = self.graph.max_degree
        st.eps = 0.0
        st.refreshes += 1
        self.cache.note_refresh(client, self._fingerprint(client), pack)
        self._logits_memo.pop(client, None)

    # -- incremental updates ------------------------------------------------

    def apply_update(self, delta: GraphDelta) -> Dict[str, Any]:
        """Absorb a graph delta: patch every resident client pack locally,
        re-measure the Thm 3.5 drift, refresh any client whose bound
        crossed ``refresh_threshold``. Returns an update report."""
        if self.method == "distgat" and delta.num_new_nodes:
            if delta.owners is None:
                raise ValueError(
                    "distgat serving needs delta.owners: new nodes must be "
                    "assigned to a client for edge visibility"
                )
            owners = np.asarray(delta.owners, np.int32).reshape(-1)
            if owners.shape[0] != delta.num_new_nodes:
                raise ValueError("delta.owners length must match new node count")
            if owners.min() < 0 or owners.max() >= self.num_clients:
                raise ValueError("delta.owners out of client range")
            self.part = Partition(
                owner=np.concatenate([self.part.owner, owners]),
                num_clients=self.part.num_clients,
                beta=self.part.beta,
            )
        old_nodes = self.graph.num_nodes
        self._set_graph(apply_delta(self.graph, delta))
        refreshed: List[int] = []
        drift: Dict[int, float] = {}
        with telemetry.span(
            "serving.apply_update",
            new_nodes=delta.num_new_nodes, new_edges=delta.num_new_edges,
        ):
            for client in sorted(self._clients):
                st = self._clients[client]
                entry = self.cache.peek(client)
                if entry is None:              # evicted: rebuilt on next query
                    del self._clients[client]
                    continue
                vis = self._visible_mask_np(client)
                if self.engine.needs_pack:
                    patch_key = jax.random.fold_in(
                        client_pack_key(self.pack_key, client), 10_000 + self._version
                    )
                    pack = patch_pack(
                        self.engine, patch_key, entry.pack, old_nodes,
                        self.graph, st.b_pack,
                        vis if self.method == "distgat" else None,
                    )
                    st.covered = extend_coverage(
                        st.covered, self.graph, st.b_pack,
                        vis if self.method == "distgat" else None,
                    )
                    st.eps = mass_drift(
                        self.params[0], self.coeffs, self.cfg.basis, self.cfg.domain,
                        self.graph, st.covered,
                        vis if self.method == "distgat" else None,
                    )
                    st.patches += 1
                    st.history.append(st.eps)
                    self.cache.note_patch(client, self._fingerprint(client), pack)
                    drift[client] = st.eps
                    if self.drift(client)["bound"] > self.refresh_threshold:
                        self.refresh(client)
                        refreshed.append(client)
                else:
                    # Pack-free engines re-read the graph arrays: exact, no drift.
                    self.cache.revalidate(client, self._fingerprint(client))
                    st.history.append(0.0)
                    drift[client] = 0.0
        return {
            "new_nodes": delta.num_new_nodes,
            "new_edges": delta.num_new_edges,
            "num_nodes": self.graph.num_nodes,
            "drift": drift,
            "refreshed": refreshed,
        }

    def drift(self, client: int) -> Dict[str, Any]:
        """Tracked Thm 3.5 drift for a client's pack: measured eps, the
        propagated logit bound, and refresh accounting."""
        st = self._clients.get(client, ClientState())
        return {
            "eps": st.eps,
            "bound": thm35_logit_bound(
                st.eps, self.cfg.num_layers, self.cfg.heads
            ),
            "threshold": self.refresh_threshold,
            "patches": st.patches,
            "refreshes": st.refreshes,
            "history": list(st.history),
        }

    # -- query path ---------------------------------------------------------

    def _client_logits(self, client: int) -> np.ndarray:
        memo = self._logits_memo.get(client)
        if memo is not None and memo[0] == self._version:
            self.cache.touch(client)
            return memo[1]
        entry = self._ensure_client(client)
        vis = self._visible_mask_np(client)
        with telemetry.span("serving.client_forward", client=client):
            logits = np.asarray(self._forward(
                self.params, entry.pack, self._h, self._idx, jnp.asarray(vis)
            ))
        self._logits_memo[client] = (self._version, logits)
        return logits

    def serve_batch(self, queries: Sequence[Query]) -> List[QueryResult]:
        """Answer a microbatch: one forward per distinct client, per-query
        logits/labels gathered from it (input order preserved)."""
        by_client: Dict[int, List[int]] = {}
        for i, q in enumerate(queries):
            if not (0 <= q.node < self.graph.num_nodes):
                raise ValueError(
                    f"node {q.node} out of range [0, {self.graph.num_nodes})"
                )
            by_client.setdefault(int(q.client), []).append(i)
        out: List[Optional[QueryResult]] = [None] * len(queries)
        with telemetry.span(
            "serving.serve_batch", queries=len(queries), clients=len(by_client)
        ):
            for client, idxs in by_client.items():
                logits = self._client_logits(client)
                for i in idxs:
                    row = logits[queries[i].node]
                    out[i] = QueryResult(
                        client=client, node=int(queries[i].node),
                        logits=row, label=int(np.argmax(row)),
                    )
        telemetry.counter("serving.queries").inc(len(queries))
        return out  # type: ignore[return-value]

    # -- persistence --------------------------------------------------------

    def save_cache(self, directory: Optional[str] = None) -> Dict[str, Any]:
        """Persist the pack cache (entries + counters) so a restarted server
        warm-starts instead of re-precomputing every pack. Writes to
        ``directory`` or the ``cache_dir`` the server was built with."""
        target = directory or self.cache_dir
        if target is None:
            raise ValueError(
                "no cache directory: pass save_cache(directory=...) or "
                "construct the server with cache_dir="
            )
        return self.cache.save(target)

    # -- reporting ----------------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        return {
            "engine": self.cfg.engine,
            "method": self.method,
            "num_clients": self.num_clients,
            "num_nodes": self.graph.num_nodes,
            "graph_version": self._version,
            "cache": self.cache.stats(),
            "drift": {c: self.drift(c) for c in sorted(self._clients)},
        }
