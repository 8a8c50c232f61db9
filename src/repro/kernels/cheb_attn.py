"""Pallas TPU kernel: fused Chebyshev/polynomial attention aggregation.

This is FedGAT's per-step compute hot spot (paper Eq. 6-7): for every node,
evaluate the degree-p polynomial on the per-edge scores and aggregate
neighbour features, all in one VMEM-resident pass —

    e_ij = sum_n q_n x_ij^n          (Horner, VPU)
    out_i = (sum_j e_ij h_j) / (sum_j e_ij)   (MXU-eligible contraction)

Isolated / fully-masked rows (den == 0, exactly — every summand is zero)
produce EXACT zeros, not NaN — ``where(den != 0, num / den, 0)`` — so
padding rows need no fake neighbours and genuinely isolated nodes are safe
on every engine path. Nonzero denominators divide exactly like the direct
oracle, whatever their sign, keeping engine parity.

TPU adaptation notes (DESIGN.md §3):
  * padded-degree dense layout (N, B): no ragged loops, lane-aligned;
  * the grid is head-batched: ([graphs,] node_block, feat_block, heads)
    with heads INNERMOST — ALL attention heads (and optionally a batch of
    same-shape graphs) aggregate in ONE ``pallas_call``, and because the
    h/mask tile indices are constant across the consecutive head steps,
    H heads stream h from HBM once per (i, j) tile sweep instead of H
    times;
  * the scores block (BN, B) is re-evaluated per feature block —
    polynomial eval is O(p·B) VPU flops, far cheaper than re-streaming h;
  * polynomial weights need NO flash-style online max: partial sums are
    plain associative adds (a structural advantage of the paper's
    polynomial scores over exp-softmax on TPU).

Block shapes default to (128 nodes, full B, 128 features) — B is padded to
a multiple of 8 by the graph layer; the feature tile meets the MXU lane
width. ``repro.kernels.ops.select_block_sizes`` autotunes these per shape.

``jax.grad`` does not flow through ``pallas_call``; the differentiable
entry is :func:`cheb_attn_diff` (``custom_vjp``: Pallas forward, pure-jnp
backward from the guarded oracle math).
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Per-call interpret-mode decision.

    Priority: explicit argument > REPRO_PALLAS_INTERPRET env var ("1"/"0",
    "true"/"false", ...) > backend default (interpret everywhere but TPU).
    Resolved at call time so the backend may change after import.
    """
    if interpret is not None:
        return bool(interpret)
    env = os.environ.get("REPRO_PALLAS_INTERPRET", "").strip()
    if env:  # empty counts as unset
        return env.lower() not in ("0", "false", "no", "off")
    return jax.default_backend() != "tpu"


def _cheb_attn_kernel(x_ref, h_ref, m_ref, q_ref, o_ref):
    # Leading grid dims (graph batch, head) arrive as size-1 block axes;
    # collapse them so one kernel body serves every grid rank.
    x = x_ref[...].reshape(x_ref.shape[-2:]).astype(jnp.float32)   # (BN, B)
    m = m_ref[...].reshape(m_ref.shape[-2:]).astype(jnp.float32)   # (BN, B)
    coeffs = q_ref[...].astype(jnp.float32)                        # (P+1,)

    # Horner evaluation of the attention polynomial (paper Eq. 6).
    p = coeffs.shape[0]
    e = jnp.zeros_like(x)
    for n in range(p - 1, -1, -1):
        e = e * x + coeffs[n]
    e = e * m                                      # mask padded neighbours

    h = h_ref[...].reshape(h_ref.shape[-3:]).astype(jnp.float32)   # (BN, B, BD)
    num = jax.lax.dot_general(
        e[:, None, :], h,
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )[:, 0, :]                                     # (BN, BD)
    den = jnp.sum(e, axis=-1, keepdims=True)       # (BN, 1)
    # Isolated/fully-masked rows sum to EXACTLY zero (every term is 0):
    # guard only that case so 0/0 becomes an exact zero row. Nonzero dens —
    # including negative out-of-domain ones — divide exactly like the
    # direct oracle, keeping engine parity.
    ok = den != 0
    out = jnp.where(ok, num / jnp.where(ok, den, 1.0), 0.0)
    o_ref[...] = out.reshape(o_ref.shape).astype(o_ref.dtype)


def cheb_attn(
    x: Array,
    h_nb: Array,
    mask: Array,
    coeffs: Array,
    *,
    block_n: int = 128,
    block_d: int = 128,
    interpret: Optional[bool] = None,
) -> Array:
    """Fused polynomial-attention aggregation; one ``pallas_call`` total.

    Three accepted layouts (``G`` = same-shape graph batch, ``H`` = heads):

      x: (N, B),       h_nb: (N, B, D),    mask: (N, B)    -> (N, D)
      x: (H, N, B),    h_nb: (N, B, D),    mask: (N, B)    -> (H, N, D)
      x: (G, H, N, B), h_nb: (G, N, B, D), mask: (G, N, B) -> (G, H, N, D)

    ``h_nb``/``mask`` are shared by all heads of a graph. Rows whose mask
    sums to zero return exact zeros. ``interpret=None`` resolves through
    :func:`resolve_interpret`: compiled on a TPU, interpreted elsewhere.
    """
    return _cheb_attn(
        x, h_nb, mask, coeffs, block_n=block_n, block_d=block_d,
        interpret=resolve_interpret(interpret),
    )


@functools.partial(jax.jit, static_argnames=("block_n", "block_d", "interpret"))
def _cheb_attn(x, h_nb, mask, coeffs, *, block_n, block_d, interpret):
    if x.ndim == 2:
        return _cheb_attn(
            x[None], h_nb, mask, coeffs,
            block_n=block_n, block_d=block_d, interpret=interpret,
        )[0]
    if x.ndim not in (3, 4):
        raise ValueError(f"x must be (N,B), (H,N,B) or (G,H,N,B); got {x.shape}")

    n, b = x.shape[-2:]
    d = h_nb.shape[-1]
    bn = min(block_n, n)
    bd = min(block_d, d)
    if n % bn or d % bd:
        raise ValueError(f"N={n} and D={d} must divide block sizes ({bn},{bd})")
    p = coeffs.shape[0]
    coeff_spec = pl.BlockSpec((p,), lambda *_: (0,))
    # The head axis is the INNERMOST (fastest-varying) grid dim: the h_nb
    # and mask tile indices are then constant across consecutive steps, so
    # Pallas fetches each neighbour-feature tile from HBM once per (i, j)
    # sweep instead of once per head. The graph-batch axis is outermost —
    # its h genuinely changes, so no reuse is possible there anyway.
    if x.ndim == 3:
        heads = x.shape[0]
        grid = (n // bn, d // bd, heads)
        in_specs = [
            pl.BlockSpec((1, bn, b), lambda i, j, h: (h, i, 0)),
            pl.BlockSpec((bn, b, bd), lambda i, j, h: (i, 0, j)),
            pl.BlockSpec((bn, b), lambda i, j, h: (i, 0)),
            coeff_spec,
        ]
        out_specs = pl.BlockSpec((1, bn, bd), lambda i, j, h: (h, i, j))
        out_shape = jax.ShapeDtypeStruct((heads, n, d), h_nb.dtype)
    else:
        graphs, heads = x.shape[:2]
        grid = (graphs, n // bn, d // bd, heads)
        in_specs = [
            pl.BlockSpec((1, 1, bn, b), lambda g, i, j, h: (g, h, i, 0)),
            pl.BlockSpec((1, bn, b, bd), lambda g, i, j, h: (g, i, 0, j)),
            pl.BlockSpec((1, bn, b), lambda g, i, j, h: (g, i, 0)),
            coeff_spec,
        ]
        out_specs = pl.BlockSpec((1, 1, bn, bd), lambda g, i, j, h: (g, h, i, j))
        out_shape = jax.ShapeDtypeStruct((graphs, heads, n, d), h_nb.dtype)
    return pl.pallas_call(
        _cheb_attn_kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(x, h_nb, mask.astype(x.dtype), coeffs)


# ---------------------------------------------------------------------------
# Differentiable entry: Pallas forward, guarded-oracle backward
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def cheb_attn_diff(
    x: Array,
    h_nb: Array,
    mask: Array,
    coeffs: Array,
    block_n: int = 128,
    block_d: int = 128,
    interpret: Optional[bool] = None,
) -> Array:
    """(H, N, B) head-batched :func:`cheb_attn` that supports ``jax.grad``.

    ``pallas_call`` has no autodiff rule, so training paths (the ``kernel``
    engine inside the federated Trainer) route through this wrapper: the
    forward is the fused kernel, the backward is ``jax.vjp`` of the guarded
    oracle math — cheap jnp contractions over the same (H, N, B) blocks.
    """
    return cheb_attn(
        x, h_nb, mask, coeffs, block_n=block_n, block_d=block_d, interpret=interpret
    )


def _cheb_attn_diff_fwd(x, h_nb, mask, coeffs, block_n, block_d, interpret):
    out = cheb_attn(
        x, h_nb, mask, coeffs, block_n=block_n, block_d=block_d, interpret=interpret
    )
    return out, (x, h_nb, mask, coeffs)


def _cheb_attn_diff_bwd(block_n, block_d, interpret, res, g):
    from repro.kernels.ref import cheb_attn_ref  # the one guarded oracle

    _, vjp = jax.vjp(cheb_attn_ref, *res)
    return vjp(g)


cheb_attn_diff.defvjp(_cheb_attn_diff_fwd, _cheb_attn_diff_bwd)
