"""Seeded stochastic-block-model graph generator, kept with the benchmark.

A copy of the generator in ``repro.graphs.synthetic.make_sbm`` (and of the
CSR helpers it calls in ``repro.graphs.graph``), taking its parameters from
a configuration file's ``graph`` block instead of a preset table, so that
the benchmark's inputs do not move when the program's generator does.
Given the ``sbm_100k`` preset's numbers it returns the same arrays as
``make_sbm("sbm_100k", seed)`` (``bench/tests/test_graphgen.py``).

Everything here is host numpy; nothing is N x N.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

GRAPH_KEYS = (
    "nodes", "features", "classes", "avg_deg_in", "avg_deg_out", "keep",
    "noise", "train_per_class", "val", "test", "degree_cap", "pad_multiple",
)


def _sample_block_edges(rng, nodes_a, nodes_b, p) -> Optional[np.ndarray]:
    """One SBM block's edges: a Binomial count placed uniformly."""
    if p <= 0.0:
        return None
    na = len(nodes_a)
    if nodes_b is None:
        pairs = na * (na - 1) // 2
        if pairs <= 0:
            return None
        m = rng.binomial(pairs, min(p, 1.0))
        if m == 0:
            return None
        i = nodes_a[rng.integers(0, na, size=m)]
        j = nodes_a[rng.integers(0, na, size=m)]
        keep = i != j
        return np.stack([i[keep], j[keep]], axis=1)
    nb = len(nodes_b)
    pairs = na * nb
    if pairs <= 0:
        return None
    m = rng.binomial(pairs, min(p, 1.0))
    if m == 0:
        return None
    i = nodes_a[rng.integers(0, na, size=m)]
    j = nodes_b[rng.integers(0, nb, size=m)]
    return np.stack([i, j], axis=1)


def _edges_to_csr(edges: np.ndarray, n: int) -> Tuple[np.ndarray, np.ndarray]:
    """Symmetrised, self-looped, deduplicated CSR with sorted rows."""
    e = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    src = np.concatenate([e[:, 0], e[:, 1], np.arange(n, dtype=np.int64)])
    dst = np.concatenate([e[:, 1], e[:, 0], np.arange(n, dtype=np.int64)])
    keys = np.unique(src * n + dst)
    rows = keys // n
    indices = (keys % n).astype(np.int32)
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(rows, minlength=n), out=indptr[1:])
    return indptr, indices


def _pad_degree(deg: int, multiple: int) -> int:
    return int(-(-deg // multiple) * multiple)


def _csr_to_padded(indptr, indices, pad_multiple, max_degree=None):
    """CSR -> (nbr_idx, nbr_mask): each row's first B neighbours."""
    n = indptr.shape[0] - 1
    degs = np.diff(indptr)
    b = int(degs.max()) if max_degree is None else int(max_degree)
    b = _pad_degree(max(b, 1), pad_multiple)
    take = np.minimum(degs, b)
    col = np.arange(b, dtype=np.int64)[None, :]
    nbr_mask = col < take[:, None]
    pos = indptr[:-1, None] + col
    gathered = indices[np.minimum(pos, indices.size - 1)]
    nbr_idx = np.where(nbr_mask, gathered, 0).astype(np.int32)
    return nbr_idx, nbr_mask


def _cap_degree(indptr, indices, max_degree, seed):
    """Every node keeps its self-loop and at most ``max_degree - 1`` other
    neighbours, drawn uniformly under ``seed``; kept ids stay ascending."""
    n = indptr.shape[0] - 1
    degs = np.diff(indptr)
    nnz = indices.shape[0]
    rows = np.repeat(np.arange(n, dtype=np.int64), degs)
    rng = np.random.default_rng(seed)
    pri = rng.random(nnz)
    pri[indices == rows] = -1.0
    order = np.lexsort((pri, rows))
    rank_sorted = np.arange(nnz, dtype=np.int64) - np.repeat(indptr[:-1], degs)
    keep = np.zeros(nnz, dtype=bool)
    keep[order] = rank_sorted < max_degree
    new_indices = indices[keep]
    new_indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(rows[keep], minlength=n), out=new_indptr[1:])
    return new_indptr, new_indices


def make_sbm(spec: Dict, seed: int) -> Dict[str, np.ndarray]:
    """The graph of a configuration's ``graph`` block under ``seed``.

    Returns the arrays of ``repro.graphs.graph.Graph`` by field name
    (``features``, ``labels``, ``indptr``, ``indices``, ``nbr_idx``,
    ``nbr_mask``, ``train_mask``, ``val_mask``, ``test_mask``) plus
    ``num_classes``.
    """
    missing = [k for k in GRAPH_KEYS if k not in spec]
    if missing:
        raise KeyError(f"graph block lacks {missing}")
    n, d, c = int(spec["nodes"]), int(spec["features"]), int(spec["classes"])
    rng = np.random.default_rng(seed)

    labels = rng.integers(0, c, size=n).astype(np.int32)
    by_class = [np.nonzero(labels == k)[0] for k in range(c)]

    blocks = []
    for c1 in range(c):
        n_c = max(len(by_class[c1]), 1)
        p_in = min(float(spec["avg_deg_in"]) / n_c, 1.0)
        blocks.append(_sample_block_edges(rng, by_class[c1], None, p_in))
        for c2 in range(c1 + 1, c):
            p_out = min(float(spec["avg_deg_out"]) / max(n - n_c, 1), 1.0)
            blocks.append(_sample_block_edges(rng, by_class[c1], by_class[c2], p_out))
    blocks = [b for b in blocks if b is not None and len(b)]
    edges = np.concatenate(blocks, axis=0) if blocks else np.zeros((0, 2), np.int64)

    words_per_class = max(3, d // (c + 1))
    signatures = np.zeros((c, d), dtype=np.float32)
    for k in range(c):
        signatures[k, rng.choice(d, size=words_per_class, replace=False)] = 1.0
    keep = rng.random((n, d), dtype=np.float32) < float(spec["keep"])
    noise = (rng.random((n, d), dtype=np.float32) < float(spec["noise"])).astype(np.float32)
    feats = signatures[labels] * keep + noise
    norms = np.linalg.norm(feats, axis=1, keepdims=True)
    feats = (feats / np.maximum(norms, 1e-6)).astype(np.float32)

    train_mask = np.zeros(n, dtype=bool)
    for k in range(c):
        idx = by_class[k].copy()
        rng.shuffle(idx)
        train_mask[idx[: int(spec["train_per_class"])]] = True
    rest = np.nonzero(~train_mask)[0]
    rng.shuffle(rest)
    n_val, n_test = int(spec["val"]), int(spec["test"])
    val_mask = np.zeros(n, dtype=bool)
    test_mask = np.zeros(n, dtype=bool)
    val_mask[rest[:n_val]] = True
    test_mask[rest[n_val : n_val + n_test]] = True

    pad = int(spec["pad_multiple"])
    indptr, indices = _edges_to_csr(edges, n)
    cap = spec["degree_cap"]
    if cap is None:
        nbr_idx, nbr_mask = _csr_to_padded(indptr, indices, pad)
    else:
        indptr, indices = _cap_degree(indptr, indices, int(cap), seed)
        nbr_idx, nbr_mask = _csr_to_padded(indptr, indices, pad, int(cap))
    return {
        "features": feats,
        "labels": labels,
        "indptr": indptr,
        "indices": indices.astype(np.int32),
        "nbr_idx": nbr_idx,
        "nbr_mask": nbr_mask,
        "train_mask": train_mask,
        "val_mask": val_mask,
        "test_mask": test_mask,
        "num_classes": c,
    }
