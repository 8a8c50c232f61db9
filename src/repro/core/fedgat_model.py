"""End-to-end FedGAT model (paper §4 "FedGAT for Multiple GAT Layers").

Layer 1 — the only layer that needs raw cross-client features — runs the
approximate FedGAT update from the pre-communicated pack. Layers l > 1 use
the exact GAT update on layer-(l-1) embeddings, which the paper permits
clients to exchange (they are highly non-linear in the inputs).

Layer-1 engines are pluggable (see repro/core/engine.py); the seeds are:
  * "matrix" — Matrix FedGAT (paper §4, Algorithm 1/2)
  * "vector" — Vector FedGAT (paper Appendix F)
  * "direct" — the mathematical oracle (same numbers, no pack; used for
                large simulations and as kernel reference)
  * "kernel" — fused Pallas polynomial-attention kernel (interpret mode on
                CPU, TPU-tiled BlockSpecs; see repro/kernels)
  * "exact"  — plain GAT (degenerate engine, for baselines)

Two API levels:
  * the :class:`FedGAT` facade — owns the config, the engine, the series
    coefficients (computed once) and the pack lifecycle:
    ``model.init(key, graph)``, ``model.precommunicate(key, graph)``,
    ``model.apply(params, graph, nbr_mask)``;
  * the original free functions (``init_params`` / ``make_pack`` /
    ``fedgat_forward``) — kept as thin wrappers over the same registry for
    backwards compatibility.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import chebyshev
from repro.core.engine import Engine, get_engine
from repro.core.gat import elu, gat_layer_nbr, init_gat_params
from repro.core.nbr_gather import NbrTable

Array = jax.Array


@dataclass(frozen=True)
class FedGATConfig:
    hidden: int = 8
    heads: int = 8
    out_heads: int = 1
    num_layers: int = 2               # >=2; layer 1 approximate, rest exact
    degree: int = 16                  # Chebyshev truncation degree p
    domain: Tuple[float, float] = (-4.0, 4.0)
    basis: str = "power"              # "power" (paper) | "chebyshev" (stable)
    engine: str = "matrix"            # layer-1 engine (registry name)
    leaky_slope: float = 0.2
    r: float = 1.7                    # projector obfuscation constant

    def coeffs(self) -> np.ndarray:
        return chebyshev.attention_series(
            self.degree, self.domain, self.leaky_slope, basis=self.basis
        )


def init_params(key: Array, d_in: int, num_classes: int, cfg: FedGATConfig):
    if cfg.num_layers <= 2:
        return init_gat_params(
            key, d_in, cfg.hidden, num_classes, cfg.heads, cfg.out_heads
        )
    # L-layer GAT: concat heads between hidden layers (paper §4 multi-layer)
    from repro.core.gat import init_gat_layer

    keys = jax.random.split(key, cfg.num_layers)
    params = [init_gat_layer(keys[0], d_in, cfg.hidden, cfg.heads)]
    for li in range(1, cfg.num_layers - 1):
        params.append(
            init_gat_layer(keys[li], cfg.hidden * cfg.heads, cfg.hidden, cfg.heads)
        )
    params.append(
        init_gat_layer(keys[-1], cfg.hidden * cfg.heads, num_classes, cfg.out_heads)
    )
    return params


def layered_forward(
    engine: Engine,
    params: Sequence[Any],
    coeffs: Optional[Array],
    pack: Optional[Any],
    h: Array,
    nbr_idx: Array,
    nbr_mask: Array,
    table: Optional[NbrTable] = None,
) -> Array:
    """Engine layer 1 + exact GAT layers l > 1 -> class logits (N, C).

    Public building block: the serving layer calls it directly with cached
    (possibly patched) packs instead of going through a facade instance.
    ``table`` (the graph's lists with :func:`~repro.core.nbr_gather.
    reverse_slots`) makes every neighbour-score and embedding gather's
    backward a gather; without it autodiff's scatter-add stands.
    """
    # Each layer runs under a named scope ("layer1", "layer2", ...), so its
    # device ops, forward and backward, carry the layer in their op names.
    with jax.named_scope("layer1"):
        x = engine.apply(
            params[0], pack, coeffs, h, nbr_idx, nbr_mask, concat=True, table=table
        )
        x = elu(x)
    # Layers > 1: exact GAT update (paper: post-layer-1 embeddings shareable).
    for li in range(1, len(params)):
        last = li == len(params) - 1
        with jax.named_scope(f"layer{li + 1}"):
            x = gat_layer_nbr(
                params[li], x, nbr_idx, nbr_mask, concat=not last, table=table
            )
            if not last:
                x = elu(x)
    return x


_layered_forward = layered_forward  # backwards-compatible private alias


class FedGAT:
    """Model facade: config + engine + coefficients + pack lifecycle.

    Typical use::

        model = FedGAT(FedGATConfig(engine="vector", degree=16))
        params = model.init(key, graph)
        model.precommunicate(pack_key, graph)   # the ONE comm round
        logits = model.apply(params, graph)     # full-graph nbr_mask
        logits = model.apply(params, graph, client_mask)

    Series coefficients are computed once at construction (not per call);
    the pre-training pack is computed once by :meth:`precommunicate` and
    reused by every :meth:`apply`.
    """

    def __init__(self, cfg: Optional[FedGATConfig] = None, **overrides):
        if cfg is None:
            cfg = FedGATConfig(**overrides)
        elif overrides:
            raise TypeError("pass either a FedGATConfig or field overrides, not both")
        self.cfg = cfg
        self.engine: Engine = get_engine(cfg.engine)(cfg)
        self.coeffs: Optional[Array] = (
            jnp.asarray(cfg.coeffs(), jnp.float32) if self.engine.needs_coeffs else None
        )
        self.pack: Optional[Any] = None
        self._pack_graph: Optional[Any] = None  # which graph the pack belongs to

    def _graph_arrays(self, graph) -> Tuple[Array, Array, Array]:
        return (
            jnp.asarray(graph.features),
            jnp.asarray(graph.nbr_idx),
            jnp.asarray(graph.nbr_mask),
        )

    def init(self, key: Array, graph):
        """Initialise GAT parameters for ``graph``'s feature/class dims."""
        return init_params(key, graph.feature_dim, graph.num_classes, self.cfg)

    def precommunicate(self, key: Array, graph) -> Optional[Any]:
        """The one-shot pre-training communication round; stores the pack."""
        h, nbr_idx, nbr_mask = self._graph_arrays(graph)
        self.pack = self.engine.precompute(key, h, nbr_idx, nbr_mask)
        self._pack_graph = graph
        return self.pack

    # -- serving hooks ------------------------------------------------------

    def install_pack(self, pack: Optional[Any], graph) -> None:
        """Adopt an externally built pack (cached or incrementally patched)
        as the pack for ``graph``. The serving layer uses this to swap a
        patched pack in without re-running :meth:`precommunicate`."""
        if pack is not None and not self.engine.needs_pack:
            raise ValueError(
                f"engine {self.cfg.engine!r} takes no pack; refusing to "
                "install one"
            )
        self.pack = pack
        self._pack_graph = graph

    def refresh_pack(self, key: Array, graph) -> Optional[Any]:
        """Full pack rebuild for ``graph`` (serving's bound-crossed path).
        Identical to :meth:`precommunicate` — same key, same graph arrays,
        bit-for-bit the same pack."""
        return self.precommunicate(key, graph)

    def apply(self, params: Sequence[Any], graph, nbr_mask: Optional[Array] = None) -> Array:
        """Forward pass -> class logits (N, C).

        ``nbr_mask`` restricts edge visibility (e.g. a client's view);
        defaults to the full-graph mask.
        """
        if self.engine.needs_pack:
            if self.pack is None:
                raise RuntimeError(
                    f"engine {self.cfg.engine!r} needs a pack: call "
                    "model.precommunicate(key, graph) before model.apply(...)"
                )
            if graph is not self._pack_graph:
                raise RuntimeError(
                    f"engine {self.cfg.engine!r}: the stored pack was "
                    "precommunicated for a different graph object; call "
                    "model.precommunicate(key, graph) for this graph first"
                )
        h, nbr_idx, full_mask = self._graph_arrays(graph)
        if nbr_mask is None:
            nbr_mask = full_mask
        return _layered_forward(
            self.engine, params, self.coeffs, self.pack, h, nbr_idx, nbr_mask
        )


# ---------------------------------------------------------------------------
# Backwards-compatible free functions (thin wrappers over the registry)
# ---------------------------------------------------------------------------

def make_pack(
    key: Array, cfg: FedGATConfig, h: Array, nbr_idx: Array, nbr_mask: Array
) -> Optional[Any]:
    """Pre-training communication round (engine-dependent payload)."""
    return get_engine(cfg.engine)(cfg).precompute(key, h, nbr_idx, nbr_mask)


def fedgat_forward(
    params: Sequence[Any],
    cfg: FedGATConfig,
    coeffs: Optional[Array],
    pack: Optional[Any],
    h: Array,
    nbr_idx: Array,
    nbr_mask: Array,
) -> Array:
    """Multi-layer FedGAT forward -> class logits (N, C)."""
    engine = get_engine(cfg.engine)(cfg)
    return _layered_forward(engine, params, coeffs, pack, h, nbr_idx, nbr_mask)
