"""jit'd wrappers exposing the Pallas kernels to the rest of the stack,
plus the per-shape block-size autotuner for the FedGAT aggregation kernel."""
from __future__ import annotations

import os
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.nbr_gather import NbrTable
from repro.kernels.cheb_attn import cheb_attn, cheb_attn_diff, resolve_interpret
from repro.kernels.flash_attn import flash_attn
from repro.kernels.poly_attn import poly_attn
from repro.kernels import ref

Array = jax.Array


# ---------------------------------------------------------------------------
# Block-size autotuning for cheb_attn
# ---------------------------------------------------------------------------

# Candidate tile edges: MXU/VPU-friendly powers of two down to the f32
# sublane width. The layer pads N and D up to the chosen multiples. Every
# block_n candidate is a multiple of 8, as Mosaic requires of a block's
# second-to-last dim; the interpreter takes any block_d.
_BLOCK_CANDIDATES = (128, 64, 32, 16, 8)
# Compiled block_d candidates besides the whole feature width: Mosaic takes
# a block's last dim only as a multiple of the 128-lane width or as the
# whole array dim.
_LANE_CANDIDATES = (512, 256, 128)
# Per-block VMEM footprint budget (x + mask + h + out tiles, f32, double
# buffered) — stay well under the ~16 MiB/core VMEM.
_VMEM_BUDGET_BYTES = 4 * 1024 * 1024
# Estimated fixed cost per grid step, in "padded-element work" units. Grid
# steps are nearly free when compiled but are Python-level iterations in
# interpret mode, so interpret weighs them much heavier — the tuner then
# prefers the coarsest legal grid.
_STEP_OVERHEAD = {False: 2_048, True: 262_144}

_BLOCK_CACHE: Dict[Tuple, Tuple[int, int]] = {}


def _pad_to(v: int, multiple: int) -> int:
    return -(-v // multiple) * multiple


def select_block_sizes(
    n: int, b: int, d: int, heads: int = 1, *, interpret: Optional[bool] = None
) -> Tuple[int, int]:
    """Choose ``(block_n, block_d)`` for :func:`cheb_attn` given the shape.

    A pure-Python cost model over the candidate tile grid: total padded
    work (the layer pads N→block_n and D→block_d multiples, so oversized
    tiles waste compute) plus a per-grid-step launch overhead (weighted
    heavily in interpret mode), subject to a VMEM footprint budget.
    ``interpret=None`` resolves through :func:`resolve_interpret`.
    Compiled, block_d is the whole width ``d`` or a multiple of 128, the
    only tiles Mosaic lowers. A shape with no tile under the budget
    raises. Memoised per process; ``REPRO_CHEB_BLOCK_N`` /
    ``REPRO_CHEB_BLOCK_D`` env vars override either edge VERBATIM (validated as positive ints,
    but exempt from the VMEM budget and divisibility checks — the
    padding-layer consumer, :func:`cheb_attn_layer`, accepts any positive
    block; callers invoking :func:`cheb_attn` directly must snap the
    result to divisors of their unpadded shape themselves).
    """
    def _env_block(var: str) -> Optional[int]:
        raw = os.environ.get(var, "").strip()
        if not raw:
            return None
        try:
            v = int(raw)
        except ValueError:
            raise ValueError(f"{var}={raw!r}: must be a positive integer") from None
        if v <= 0:
            raise ValueError(f"{var}={raw!r}: must be a positive integer")
        return v

    interpret = resolve_interpret(interpret)
    env_n = _env_block("REPRO_CHEB_BLOCK_N")
    env_d = _env_block("REPRO_CHEB_BLOCK_D")
    key = (n, b, d, heads, interpret, env_n, env_d)
    hit = _BLOCK_CACHE.get(key)
    if hit is not None:
        return hit

    overhead = _STEP_OVERHEAD[interpret]
    d_candidates = (
        _BLOCK_CANDIDATES if interpret
        else sorted({d, *_LANE_CANDIDATES}, reverse=True)
    )
    best, best_cost = None, None
    for bn in _BLOCK_CANDIDATES:
        for bd in d_candidates:
            vmem = 4 * (bn * b          # x tile
                        + bn * b        # mask tile
                        + bn * b * bd   # h tile
                        + bn * bd)      # out tile
            if vmem > _VMEM_BUDGET_BYTES:
                continue
            pn, pd = _pad_to(n, bn), _pad_to(d, bd)
            steps = heads * (pn // bn) * (pd // bd)
            work = heads * pn * b * pd
            cost = work + steps * overhead
            # Tie-break toward coarser tiles (fewer, larger DMAs).
            if best_cost is None or cost < best_cost or (
                cost == best_cost and bn * bd > best[0] * best[1]
            ):
                best, best_cost = (bn, bd), cost
    if best is None:
        raise ValueError(
            f"cheb_attn: no (block_n, block_d) tile for N={n}, B={b}, d={d}, "
            f"heads={heads} fits the {_VMEM_BUDGET_BYTES} B VMEM budget "
            f"(interpret={interpret})"
        )
    if env_n is not None:
        best = (env_n, best[1])
    if env_d is not None:
        best = (best[0], env_d)
    _BLOCK_CACHE[key] = best
    return best


def clear_block_cache() -> None:
    """Drop the autotune memo (tests / after env override changes)."""
    _BLOCK_CACHE.clear()


# ---------------------------------------------------------------------------
# FedGAT layer-1 via the fused kernel
# ---------------------------------------------------------------------------

def cheb_attn_layer(
    params: Dict,
    coeffs: Array,
    h: Array,
    nbr_idx: Array,
    nbr_mask: Array,
    *,
    basis: str = "power",
    domain: Tuple[float, float] = (-4.0, 4.0),
    concat: bool = True,
    interpret: Optional[bool] = None,
    block_n: Optional[int] = None,
    block_d: Optional[int] = None,
    table: Optional[NbrTable] = None,
) -> Array:
    """FedGAT layer-1 via the fused Pallas kernel ("kernel" engine).

    Pads N and d to block multiples (``block_n``/``block_d`` when given,
    autotuned per shape otherwise), aggregates ALL heads in one
    head-batched ``pallas_call``, and applies the output projection W —
    numerically the direct oracle (ref.py). Differentiable: the forward is
    the kernel, the backward is the guarded oracle math (``custom_vjp``).
    Padding rows are fully masked and come out as exact zeros (no fake
    neighbours needed), as do genuinely isolated nodes. ``table`` goes to
    :func:`~repro.core.poly_attention.edge_scores`.
    """
    if basis != "power":
        raise ValueError("kernel engine evaluates the monomial (power) basis")
    from repro.core.poly_attention import edge_scores, head_projections

    interp = resolve_interpret(interpret)
    n, d = h.shape
    b1, b2 = head_projections(params)
    x = edge_scores(b1, b2, h, nbr_idx, table)           # (H, N, B)
    mask_f = nbr_mask.astype(h.dtype)                    # (N, B)
    with jax.named_scope("nbr_gather"):
        h_nb = h[nbr_idx] * mask_f[..., None]            # (N, B, d)

    if block_n is None or block_d is None:
        auto_n, auto_d = select_block_sizes(
            n, x.shape[-1], d, heads=x.shape[0], interpret=interp
        )
        block_n = block_n or auto_n
        block_d = block_d or auto_d
    pad_n = (-n) % block_n
    pad_d = (-d) % block_d
    xp = jnp.pad(x, ((0, 0), (0, pad_n), (0, 0)))
    hp = jnp.pad(h_nb, ((0, pad_n), (0, 0), (0, pad_d)))
    mp = jnp.pad(mask_f, ((0, pad_n), (0, 0)))           # padded rows: den=0 -> 0
    # Materialise the gathered neighbour features: left fusible, the gather
    # meets the backward's contractions, and at 1e5 nodes the TPU compiler
    # then spends many minutes on that fusion instead of ~20 s.
    hp, mp = jax.lax.optimization_barrier((hp, mp))

    agg = cheb_attn_diff(
        xp, hp, mp, jnp.asarray(coeffs, jnp.float32),
        min(block_n, n + pad_n), min(block_d, d + pad_d), interp,
    )[:, :n, :d]                                          # (H, N, d)
    out = jnp.einsum("hnd,hdo->hno", agg, params["W"])    # (H, N, d_out)
    if concat:
        return jnp.transpose(out, (1, 0, 2)).reshape(n, -1)
    return out.mean(axis=0)


# ---------------------------------------------------------------------------
# Degree-bucketed launch plan: bound padded-B waste on skewed-degree graphs
# ---------------------------------------------------------------------------

def degree_bucket_plan(
    nbr_mask: np.ndarray, *, pad_multiple: int = 8, max_buckets: int = 4
) -> List[Tuple[np.ndarray, int]]:
    """Partition rows into degree buckets for :func:`cheb_attn_layer_bucketed`.

    One flat (N, B) launch pays O(N * B) padded work even when B is set by a
    handful of hubs. This groups rows by degree into at most ``max_buckets``
    buckets with power-of-two neighbour capacities (``pad_multiple`` * 2^k,
    topped by B), so each row's padded slots are within 2x of its degree
    instead of within B. Returns ``[(row_indices, b_cap), ...]`` covering
    every row exactly once (empty buckets dropped).

    Host-side only: degrees must be CONCRETE (a NumPy mask, outside jit) —
    the federated engines trace client visibility masks, so they keep the
    flat launch; this path serves centralised/serving forwards where the
    static graph mask is known at trace time.
    """
    mask = np.asarray(nbr_mask)
    deg = mask.sum(axis=1).astype(np.int64)
    B = mask.shape[1]
    caps = []
    c = max(pad_multiple, 1)
    while c < B:
        caps.append(c)
        c *= 2
    caps.append(B)
    if len(caps) > max_buckets:
        caps = caps[-max_buckets:]      # merge the smallest-degree buckets
    plan = []
    prev = -1                            # first bucket swallows deg-0 rows
    for cap in caps:
        rows = np.nonzero((deg > prev) & (deg <= cap))[0]
        if len(rows):
            plan.append((rows, int(cap)))
        prev = cap
    return plan


def cheb_attn_layer_bucketed(
    params: Dict,
    coeffs: Array,
    h: Array,
    nbr_idx: np.ndarray,
    nbr_mask: np.ndarray,
    *,
    plan: Optional[List[Tuple[np.ndarray, int]]] = None,
    basis: str = "power",
    concat: bool = True,
    interpret: Optional[bool] = None,
) -> Array:
    """:func:`cheb_attn_layer` with a degree-bucketed grid: one pallas_call
    per degree bucket, each with its neighbour axis trimmed to the bucket
    capacity. Output is bit-identical to the flat launch (same kernel, same
    reduction order per row — padded slots contribute exact zeros either
    way); total padded work drops from O(N * B_max) to ~O(sum_i 2 deg_i).

    ``nbr_idx``/``nbr_mask`` must be concrete (NumPy): trimming relies on
    valid slots forming a prefix of each padded row, which `csr_to_padded`
    guarantees.
    """
    if basis != "power":
        raise ValueError("kernel engine evaluates the monomial (power) basis")
    from repro.core.poly_attention import head_projections

    interp = resolve_interpret(interpret)
    nbr_idx = np.asarray(nbr_idx)
    nbr_mask = np.asarray(nbr_mask)
    if plan is None:
        plan = degree_bucket_plan(nbr_mask)
    n, d = h.shape
    b1, b2 = head_projections(params)
    s1 = jnp.einsum("nd,hd->hn", h, b1)                   # (H, N)
    s2 = jnp.einsum("nd,hd->hn", h, b2)
    heads = s1.shape[0]
    co = jnp.asarray(coeffs, jnp.float32)

    agg = jnp.zeros((heads, n, d), dtype=h.dtype)
    for rows, cap in plan:
        nb = nbr_idx[rows, :cap]                          # (n_k, cap)
        mask_f = jnp.asarray(nbr_mask[rows, :cap], h.dtype)
        with jax.named_scope("nbr_gather"):
            s2_nb = s2[:, nb]
            h_nb = h[nb] * mask_f[..., None]              # (n_k, cap, d)
        x = s1[:, rows, None] + s2_nb                     # (H, n_k, cap)

        nk = len(rows)
        block_n, block_d = select_block_sizes(
            nk, cap, d, heads=heads, interpret=interp
        )
        pad_n = (-nk) % block_n
        pad_d = (-d) % block_d
        xp = jnp.pad(x, ((0, 0), (0, pad_n), (0, 0)))
        hp = jnp.pad(h_nb, ((0, pad_n), (0, 0), (0, pad_d)))
        mp = jnp.pad(mask_f, ((0, pad_n), (0, 0)))
        part = cheb_attn_diff(
            xp, hp, mp, co,
            min(block_n, nk + pad_n), min(block_d, d + pad_d), interp,
        )[:, :nk, :d]
        agg = agg.at[:, rows, :].set(part)

    out = jnp.einsum("hnd,hdo->hno", agg, params["W"])
    if concat:
        return jnp.transpose(out, (1, 0, 2)).reshape(n, -1)
    return out.mean(axis=0)


__all__ = [
    "cheb_attn",
    "cheb_attn_diff",
    "flash_attn",
    "poly_attn",
    "cheb_attn_layer",
    "cheb_attn_layer_bucketed",
    "degree_bucket_plan",
    "ref",
    "resolve_interpret",
    "select_block_sizes",
    "clear_block_cache",
]
