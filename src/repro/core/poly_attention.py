"""Direct polynomial-attention oracle.

This computes exactly what the FedGAT moment machinery computes —
``e_ij ~= series(x_ij)`` with ``x_ij = b1.h_i + b2.h_j`` and the update
Eq. (7) — but *directly* from per-edge quantities, with no projector
matrices. It is:

* the mathematical oracle the Matrix/Vector FedGAT paths must match
  bit-for-bit (up to float error) in tests,
* the `ref.py` oracle for the fused Pallas kernel,
* the fast "simulation mode" engine for large federated experiments (same
  numbers as FedGAT, without materialising the O(B^3) communication pack).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.chebyshev import eval_chebyshev, eval_power_series
from repro.core.nbr_gather import NbrTable, nbr_gather

Array = jax.Array
Params = Dict[str, Array]


def head_projections(params: Params) -> Tuple[Array, Array]:
    """b1 = W^T a1, b2 = W^T a2 per head (paper Eq. 4). Returns (H, d_in)."""
    b1 = jnp.einsum("hdo,ho->hd", params["W"], params["a1"])
    b2 = jnp.einsum("hdo,ho->hd", params["W"], params["a2"])
    return b1, b2


def edge_scores(
    b1: Array, b2: Array, h: Array, nbr_idx: Array, table: Optional[NbrTable] = None
) -> Array:
    """x_ij = b1.h_i + b2.h_j over padded neighbour lists. -> (H, N, B).

    With ``table`` (:func:`~repro.core.nbr_gather.nbr_gather`) the graph's
    padding slots read s1 alone, and the gather's backward is a gather."""
    s1 = jnp.einsum("nd,hd->hn", h, b1)
    s2 = jnp.einsum("nd,hd->hn", h, b2)
    return s1[:, :, None] + nbr_gather(s2, nbr_idx, table, axis=1)


def eval_series(coeffs: Array, x: Array, basis: str, domain: Tuple[float, float]) -> Array:
    if basis == "power":
        return eval_power_series(coeffs, x)
    if basis == "chebyshev":
        return eval_chebyshev(coeffs, x, domain)
    raise ValueError(f"unknown basis {basis!r}")


def moments_direct(x: Array, h_nb: Array, mask: Array, max_n: int) -> Tuple[Array, Array]:
    """E^(n) = sum_j x_ij^n h_j, F^(n) = sum_j x_ij^n (paper Eq. 8).

    x: (..., B), h_nb: (..., B, d), mask: (..., B) ->
    E: (max_n+1, ..., d), F: (max_n+1, ...).
    """
    m = mask.astype(x.dtype)

    def body(xp, _):
        E = jnp.einsum("...b,...bd->...d", xp * m, h_nb)
        F = jnp.sum(xp * m, axis=-1)
        return xp * x, (E, F)

    _, (E, F) = jax.lax.scan(body, jnp.ones_like(x), None, length=max_n + 1)
    return E, F


def poly_gat_layer(
    params: Params,
    coeffs: Array,
    h: Array,
    nbr_idx: Array,
    nbr_mask: Array,
    *,
    basis: str = "power",
    domain: Tuple[float, float] = (-4.0, 4.0),
    concat: bool = True,
    table: Optional[NbrTable] = None,
) -> Array:
    """Approximate GAT layer via the truncated series (paper Eq. 7).

    Numerically identical to what a FedGAT client computes from its
    pre-communicated pack. h: (N, d_in) -> (N, H*d_out) or (N, d_out).
    """
    b1, b2 = head_projections(params)
    x = edge_scores(b1, b2, h, nbr_idx, table)               # (H, N, B)
    e = eval_series(coeffs, x, basis, domain)
    e = e * nbr_mask[None].astype(e.dtype)
    den = jnp.sum(e, axis=-1)[..., None]                     # (H, N, 1)
    with jax.named_scope("nbr_gather"):
        h_nb = h[nbr_idx]                                    # (N, B, d_in)
    num = jnp.einsum("hnb,nbd->hnd", e, h_nb)                # (H, N, d_in)
    # Isolated/fully-masked rows sum to exactly zero: aggregate to zero
    # instead of 0/0 NaN — the same guard as the kernel engine (ref.py),
    # keeping kernel/direct parity on degree-0 nodes.
    ok = den != 0
    agg = jnp.where(ok, num / jnp.where(ok, den, 1.0), 0.0)
    out = jnp.einsum("hnd,hdo->hno", agg, params["W"])       # (H, N, d_out)
    if concat:
        return jnp.transpose(out, (1, 0, 2)).reshape(h.shape[0], -1)
    return out.mean(axis=0)
