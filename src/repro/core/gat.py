"""Reference (centralised) Graph Attention Network — paper Eq. (1)-(3).

This is the exact model FedGAT approximates; it is both the accuracy
upper-bound baseline in the experiments (Table 1) and the numerical oracle
for the approximation-error tests (Theorems 3-5).

Two equivalent forwards are provided:
* ``gat_layer_dense``  — dense (N, N) adjacency masked softmax;
* ``gat_layer_nbr``    — padded neighbour-list gather (the representation
                          FedGAT and the Pallas kernel use).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.nbr_gather import NbrTable, nbr_gather

Array = jax.Array
Params = Dict[str, Array]

LEAKY_SLOPE = 0.2


def leaky_relu(x: Array, slope: float = LEAKY_SLOPE) -> Array:
    return jnp.where(x >= 0, x, slope * x)


def elu(x: Array) -> Array:
    return jnp.where(x > 0, x, jnp.expm1(x))


def init_gat_layer(key: Array, d_in: int, d_out: int, heads: int, scale: float = 0.5) -> Params:
    """Glorot-ish init, scaled down so Assumption 2 (norm <= 1) loosely holds."""
    kw, k1, k2 = jax.random.split(key, 3)
    lim = scale * jnp.sqrt(6.0 / (d_in + d_out))
    return {
        "W": jax.random.uniform(kw, (heads, d_in, d_out), minval=-lim, maxval=lim),
        "a1": jax.random.uniform(k1, (heads, d_out), minval=-lim, maxval=lim),
        "a2": jax.random.uniform(k2, (heads, d_out), minval=-lim, maxval=lim),
    }


def init_gat_params(
    key: Array, d_in: int, hidden: int, num_classes: int, heads: int = 8, out_heads: int = 1
) -> List[Params]:
    k1, k2 = jax.random.split(key)
    return [
        init_gat_layer(k1, d_in, hidden, heads),
        init_gat_layer(k2, hidden * heads, num_classes, out_heads),
    ]


# ---------------------------------------------------------------------------
# Dense-adjacency forward
# ---------------------------------------------------------------------------

def gat_layer_dense(params: Params, h: Array, adj: Array, concat: bool) -> Array:
    """h: (N, d_in), adj: (N, N) bool. Returns (N, heads*d_out) or (N, d_out)."""
    z = jnp.einsum("nd,hdo->hno", h, params["W"])          # (H, N, d_out)
    s1 = jnp.einsum("hno,ho->hn", z, params["a1"])          # score of dst i
    s2 = jnp.einsum("hno,ho->hn", z, params["a2"])          # score of src j
    logits = leaky_relu(s1[:, :, None] + s2[:, None, :])    # (H, N, N), ij
    logits = jnp.where(adj[None], logits, -jnp.inf)
    alpha = jax.nn.softmax(logits, axis=-1)
    alpha = jnp.where(adj[None], alpha, 0.0)
    out = jnp.einsum("hnm,hmo->hno", alpha, z)              # (H, N, d_out)
    if concat:
        return jnp.transpose(out, (1, 0, 2)).reshape(h.shape[0], -1)
    return out.mean(axis=0)


# ---------------------------------------------------------------------------
# Neighbour-list forward (identical math; FedGAT's representation)
# ---------------------------------------------------------------------------

def gat_layer_nbr(
    params: Params, h: Array, nbr_idx: Array, nbr_mask: Array, concat: bool,
    table: Optional[NbrTable] = None,
) -> Array:
    """h: (N, d_in), nbr_idx/nbr_mask: (N, B); ``table`` (the graph's
    lists, see :func:`~repro.core.nbr_gather.nbr_gather`) makes the
    gathers' backward a gather."""
    z = jnp.einsum("nd,hdo->hno", h, params["W"])           # (H, N, d_out)
    # The scores read the op's z, so z's gradient adds its slots onto theirs.
    z, z_nb = nbr_gather(z, nbr_idx, table, axis=1, with_x=True)  # (H, N, B, d_out)
    s1 = jnp.einsum("hno,ho->hn", z, params["a1"])          # (H, N)
    s2 = jnp.einsum("hno,ho->hn", z, params["a2"])          # (H, N)
    s2_nb = nbr_gather(s2, nbr_idx, table, axis=1)           # (H, N, B)
    logits = leaky_relu(s1[:, :, None] + s2_nb)              # (H, N, B)
    logits = jnp.where(nbr_mask[None], logits, -jnp.inf)
    alpha = jax.nn.softmax(logits, axis=-1)
    alpha = jnp.where(nbr_mask[None], alpha, 0.0)
    out = jnp.einsum("hnb,hnbo->hno", alpha, z_nb)
    if concat:
        return jnp.transpose(out, (1, 0, 2)).reshape(h.shape[0], -1)
    return out.mean(axis=0)


def gat_forward(
    params: Sequence[Params], h: Array, adj: Array, *, use_nbr: bool = False,
    nbr_idx: Array | None = None, nbr_mask: Array | None = None,
) -> Array:
    """Two-layer GAT: ELU between layers, raw logits out."""
    layer = (
        (lambda p, x, c: gat_layer_nbr(p, x, nbr_idx, nbr_mask, c))
        if use_nbr
        else (lambda p, x, c: gat_layer_dense(p, x, adj, c))
    )
    x = h
    for li, p in enumerate(params):
        last = li == len(params) - 1
        x = layer(p, x, not last)
        if not last:
            x = elu(x)
    return x


# ---------------------------------------------------------------------------
# Loss / metrics
# ---------------------------------------------------------------------------

def masked_cross_entropy(logits: Array, labels: Array, mask: Array) -> Array:
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), axis=-1)[:, 0]
    mask = mask.astype(logits.dtype)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def masked_accuracy(logits: Array, labels: Array, mask: Array) -> Array:
    pred = jnp.argmax(logits, axis=-1)
    correct = (pred == labels).astype(jnp.float32)
    mask = mask.astype(jnp.float32)
    return jnp.sum(correct * mask) / jnp.maximum(jnp.sum(mask), 1.0)
