"""Neighbour gathers whose backward is a gather, not a scatter-add.

A GAT layer over padded neighbour lists reads ``x[..., nbr_idx, ...]``:
every node's row at each of its B slots. Autodiff transposes that gather
into a scatter-add over all N*B slots, padding included (padding slots name
node 0, so each is a read-modify-write of node 0's row), and the TPU runs a
scatter-add as a serial loop over its updates.

:func:`nbr_gather` with a :class:`NbrTable` is the masked gather
``where(mask, x[nbr_idx], 0)`` with its exact transpose as backward: node
j's gradient is the sum of the cotangent over the valid slots that name j,
read through the reverse-slot tables that :func:`reverse_slots` builds from
the graph's concrete lists. Both directions are gathers. Without a table it
is the plain gather, and autodiff's scatter-add stands.

Each call counts, when it is traced, which backward it was built with:
``nbr_gather.gather_vjp`` (a table) or ``nbr_gather.scatter_vjp`` (none).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.graphs.graph import pad_degree

Array = jax.Array


class NbrTable(NamedTuple):
    """The graph's own lists, for :func:`nbr_gather`'s backward.

    ``mask`` is the graph's (N, B) ``nbr_mask``, never a client's view of
    it (a subset, which callers apply after the gather); ``rev`` and
    ``overflow`` are :func:`reverse_slots` of the graph's lists."""

    mask: Array
    rev: Array
    overflow: Array


def reverse_slots(nbr_idx, nbr_mask) -> Tuple[np.ndarray, np.ndarray]:
    """The valid slots that name each node, as two int32 tables.

    Slots are numbered ``n * B + b``, and ``N * B`` stands for none.
    ``rev`` is (N, B): row j lists the slots naming node j, ascending. A
    node named by more than B slots keeps its first B - 1 in its row and
    ends the row with ``N * B + 1 + k``, which stands for the sum of row k
    of ``overflow``, where the rest are listed. Both widths come from B, not
    from the largest in-degree, so graphs of one kind share one shape and
    one compiled program: ``overflow`` is (M, W), the count of such nodes
    and their largest excess, each rounded up to a multiple of 8.

    Host-side: the lists must be concrete (NumPy or device arrays, not
    tracers)."""
    idx = np.asarray(nbr_idx)
    mask = np.asarray(nbr_mask, dtype=bool)
    if idx.ndim != 2 or idx.shape != mask.shape or idx.shape[1] < 1:
        raise ValueError(
            f"nbr_idx {idx.shape} and nbr_mask {mask.shape} must be the same (N, B), B >= 1"
        )
    n, b = idx.shape
    slots = np.flatnonzero(mask)
    dst = idx.reshape(-1)[slots].astype(np.int64)
    if slots.size and (dst.min() < 0 or dst.max() >= n):
        raise ValueError(f"a valid slot names a node outside [0, {n})")
    counts = np.bincount(dst, minlength=n)
    spilled = np.flatnonzero(counts > b)
    m_pad = pad_degree(max(len(spilled), 1))
    w_pad = pad_degree(max(int(counts.max(initial=0)) - (b - 1), 1))
    if n * b + 1 + m_pad > np.iinfo(np.int32).max:
        raise ValueError(f"{n} x {b} slots do not fit int32 slot numbers")

    order = np.argsort(dst, kind="stable")          # by node, slots ascending
    node, slot = dst[order], slots[order]
    col = np.arange(slots.size) - np.repeat(np.cumsum(counts) - counts, counts)
    row_of = np.full(n, -1, np.int64)
    row_of[spilled] = np.arange(len(spilled))
    late = (row_of[node] >= 0) & (col >= b - 1)     # listed in ``overflow``

    rev = np.full((n, b), n * b, np.int32)
    rev[node[~late], col[~late]] = slot[~late]
    rev[spilled, b - 1] = n * b + 1 + np.arange(len(spilled))
    overflow = np.full((m_pad, w_pad), n * b, np.int32)
    overflow[row_of[node[late]], col[late] - (b - 1)] = slot[late]
    return rev, overflow


def _index(x: Array, idx: Array, axis: int) -> Array:
    return x[(slice(None),) * axis + (idx,)]


def _sum_rows(x: Array, table: Array, axis: int, start: Optional[Array] = None) -> Array:
    """``start`` plus ``x`` at each row of ``table`` along ``axis``, added
    in the row's column order (a node's slots ascending, the order a
    scatter-add takes); entries past the end of ``x`` read 0."""
    terms = jnp.take(x, table.T, axis=axis, mode="fill", fill_value=0)  # (..., W, R, ...)
    out = start
    for w in range(table.shape[1]):
        term = terms[(slice(None),) * axis + (w,)]
        out = term if out is None else out + term
    return out


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def _masked_gather(x, nbr_idx, mask, rev, overflow, axis):
    m = mask.reshape(mask.shape + (1,) * (x.ndim - axis - 1))
    return x, jnp.where(m, _index(x, nbr_idx, axis), 0)


def _masked_gather_fwd(x, nbr_idx, mask, rev, overflow, axis):
    return _masked_gather(x, nbr_idx, mask, rev, overflow, axis), (rev, overflow)


def _masked_gather_bwd(axis, tables, cts):
    # ct: (..., N, B, ...), read as (..., N*B, ...). Each node's gradient is
    # x's other cotangent plus its slots in ``rev``'s order ("none" and the
    # references to ``overflow`` read 0), then its ``overflow`` row's sum.
    rev, overflow = tables
    ct_x, ct = cts
    n, b = ct.shape[axis], ct.shape[axis + 1]
    flat = ct.reshape(ct.shape[:axis] + (n * b,) + ct.shape[axis + 2:])
    k = rev[:, b - 1] - (n * b + 1)                 # overflow row, or < 0
    spilled = jnp.where(k >= 0, k, overflow.shape[0])
    late = jnp.take(_sum_rows(flat, overflow, axis), spilled, axis=axis,
                    mode="fill", fill_value=0)
    return _sum_rows(flat, rev, axis, ct_x) + late, None, None, None, None


_masked_gather.defvjp(_masked_gather_fwd, _masked_gather_bwd)


def nbr_gather(
    x: Array, nbr_idx: Array, table: Optional[NbrTable] = None, *,
    axis: int = 0, with_x: bool = False,
):
    """``x`` gathered along ``axis`` at the (N, B) lists:
    ``(..., N, ...) -> (..., N, B, ...)``.

    With ``table``, slots outside ``table.mask`` read 0 and the backward is
    a gather over the valid slots; without, it is ``x[..., nbr_idx, ...]``
    and its backward autodiff's scatter-add. With ``with_x`` it returns
    ``(x, gathered)``: where x has other uses, give them this x, and x's
    gradient adds each slot onto theirs in slot order, as the scatter-add
    does (XLA folds the sum of the two into the scatter's initial value)."""
    with jax.named_scope("nbr_gather"):
        if table is None:
            telemetry.counter("nbr_gather.scatter_vjp").inc()
            out = x, _index(x, nbr_idx, axis)
        else:
            telemetry.counter("nbr_gather.gather_vjp").inc()
            out = _masked_gather(x, nbr_idx, *table, axis)
    return out if with_x else out[1]
