"""Plain reference of FedGAT's model: a GAT whose first layer scores
neighbours with the degree-p power series of exp(LeakyReLU(x)) fitted on
[-R, R] (FedGAT, arXiv:2412.16144, Eq. 5-7), exact GAT layers after it
(Velickovic et al., arXiv:1710.10903).

Written from the papers, in straightforward ``jax.numpy`` over the padded
neighbour lists; it imports nothing of ``repro``. It computes in the type
of the arrays ``prepare`` and ``init`` are given: float32, every
contraction at ``Precision.HIGHEST``; or bfloat16 throughout for the
control (contractions accumulate in float32 and round their result).
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np


def _ein(subscripts: str, a, b):
    return jnp.einsum(subscripts, a, b, precision=jax.lax.Precision.HIGHEST)


def series_coeffs(model: Dict) -> np.ndarray:
    """Monomial coefficients q_n of the Chebyshev interpolant of
    exp(LeakyReLU(x)) on the symmetric domain (paper Eq. 5 -> 6), float64."""
    lo, hi = (float(v) for v in model["domain"])
    if not np.isclose(-lo, hi):
        raise ValueError("the power basis needs a symmetric domain")
    if model["basis"] != "power":
        raise ValueError(f"basis {model['basis']!r}: only 'power' is referenced")
    n = int(model["degree"]) + 1
    k = np.arange(n, dtype=np.float64)
    t = np.cos((2 * k + 1) * np.pi / (2 * n))
    x = hi * t
    slope = float(model["leaky_slope"])
    y = np.exp(np.where(x >= 0, x, slope * x))
    c = 2.0 / n * (np.cos(np.outer(np.arange(n), (2 * k + 1) * np.pi / (2 * n))) @ y)
    c[0] *= 0.5
    q_t = np.polynomial.chebyshev.cheb2poly(c)
    return q_t * hi ** -np.arange(n, dtype=np.float64)


def _layer_init(key, d_in: int, d_out: int, heads: int) -> Dict:
    kw, k1, k2 = jax.random.split(key, 3)
    lim = 0.5 * jnp.sqrt(6.0 / (d_in + d_out))
    return {
        "W": jax.random.uniform(kw, (heads, d_in, d_out), minval=-lim, maxval=lim),
        "a1": jax.random.uniform(k1, (heads, d_out), minval=-lim, maxval=lim),
        "a2": jax.random.uniform(k2, (heads, d_out), minval=-lim, maxval=lim),
    }


def init(key, d_in: int, num_classes: int, program: Dict) -> List[Dict]:
    """Glorot-uniform at half scale, as FedGAT's code initialises a
    two-layer GAT: hidden x heads concatenated, then the class layer."""
    model = program["model"]
    if int(model["num_layers"]) != 2:
        raise ValueError("the reference covers the two-layer GAT")
    k1, k2 = jax.random.split(key)
    hidden, heads = int(model["hidden"]), int(model["heads"])
    return [
        _layer_init(k1, d_in, hidden, heads),
        _layer_init(k2, hidden * heads, num_classes, int(model["out_heads"])),
    ]


def prepare(graph: Dict, program: Dict, dtype=jnp.float32) -> Dict:
    """Device arrays the forward reads, the features and the series in
    ``dtype``."""
    model = program["model"]
    return {
        "h": jnp.asarray(graph["features"], dtype),
        "nbr_idx": jnp.asarray(graph["nbr_idx"]),
        "mask": jnp.asarray(graph["nbr_mask"]),
        "q": jnp.asarray(series_coeffs(model), dtype),
    }


def _elu(x):
    return jnp.where(x > 0, x, jnp.expm1(x))


def forward(params: List[Dict], prep: Dict, program: Dict) -> jax.Array:
    """Class logits (N, C), in the type of ``prep``'s features."""
    model = program["model"]
    slope = float(model["leaky_slope"])
    h = prep["h"]
    idx, mask = prep["nbr_idx"], prep["mask"]
    m = mask.astype(h.dtype)
    n = h.shape[0]

    # Layer 1: e_ij = sum_n q_n x_ij^n with x_ij = b1.h_i + b2.h_j, and
    # out_i = W^T (sum_j e_ij h_j / sum_j e_ij)   (paper Eq. 4-7).
    p1 = params[0]
    b1 = _ein("hdo,ho->hd", p1["W"], p1["a1"])
    b2 = _ein("hdo,ho->hd", p1["W"], p1["a2"])
    s1 = _ein("nd,hd->hn", h, b1)
    s2 = _ein("nd,hd->hn", h, b2)
    x = s1[:, :, None] + s2[:, idx]                           # (H, N, B)
    q = prep["q"]
    e = jnp.zeros_like(x)
    for k in range(q.shape[0] - 1, -1, -1):
        e = e * x + q[k]
    e = e * m[None]
    den = jnp.sum(e, axis=-1, keepdims=True)
    num = _ein("hnb,nbd->hnd", e, h[idx] * m[..., None])
    ok = den != 0
    agg = jnp.where(ok, num / jnp.where(ok, den, 1), 0)
    out = _ein("hnd,hdo->hno", agg, p1["W"])
    z1 = _elu(jnp.transpose(out, (1, 0, 2)).reshape(n, -1))

    # Layer 2: exact GAT attention over the same neighbour lists.
    p2 = params[1]
    z = _ein("nd,hdo->hno", z1, p2["W"])
    t1 = _ein("hno,ho->hn", z, p2["a1"])
    t2 = _ein("hno,ho->hn", z, p2["a2"])
    logit = t1[:, :, None] + t2[:, idx]
    logit = jnp.where(logit >= 0, logit, slope * logit)
    logit = jnp.where(mask[None], logit, -jnp.inf)
    alpha = jnp.where(mask[None], jax.nn.softmax(logit, axis=-1), 0)
    out2 = _ein("hnb,hnbo->hno", alpha, z[:, idx, :])
    return out2.mean(axis=0)
