"""repro.serving — federated graph inference service.

Serves node-classification queries from a trained FedGAT checkpoint:

* :class:`PackCache` — each client's one-shot pre-communicated pack, keyed
  by a graph-partition fingerprint, with hit/miss/patch/refresh accounting;
* :class:`GraphInferenceServer` — loads Trainer checkpoints (params +
  ``FedGATConfig`` + ``PrivacyConfig`` provenance), routes batched queries
  across clients through the head-batched ``cheb_attn`` kernel engine
  (falling back to ``direct`` when Pallas is unavailable);
* :class:`GraphDelta` / :func:`apply_delta` — incremental graph updates:
  new nodes and edges are absorbed with a cheap local pack patch, the
  accumulated approximation error is tracked against the paper's Thm 3.5
  bound (``repro.analysis.error_bounds``) and a full per-client pack
  refresh fires only when the bound is crossed;
* :class:`MicroBatcher` — size/deadline microbatching with p50/p99 latency
  and throughput accounting.
"""
from repro.serving.cache import PackCache, PackEntry, graph_fingerprint
from repro.serving.checkpoint import ServingCheckpoint, load_bundle, save_bundle
from repro.serving.scheduler import LatencyStats, MicroBatcher
from repro.serving.server import (
    GraphInferenceServer,
    Query,
    QueryResult,
    client_pack_key,
)
from repro.serving.updates import (
    Coverage,
    GraphDelta,
    apply_delta,
    concat_pack_rows,
    coverage_lookup,
    extend_coverage,
    initial_coverage,
    mass_drift,
    patch_pack,
)

__all__ = [
    "Coverage",
    "GraphDelta",
    "GraphInferenceServer",
    "LatencyStats",
    "MicroBatcher",
    "PackCache",
    "PackEntry",
    "Query",
    "QueryResult",
    "ServingCheckpoint",
    "apply_delta",
    "client_pack_key",
    "concat_pack_rows",
    "coverage_lookup",
    "extend_coverage",
    "graph_fingerprint",
    "initial_coverage",
    "load_bundle",
    "mass_drift",
    "patch_pack",
    "save_bundle",
]
