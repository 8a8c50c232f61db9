"""The comparison that decides ``correct``: what the window's own
``Trainer.run`` returned against the plain reference followed over the
same rounds from the same seed.

Every number is a gap that is 0 for a program that computes exactly what
the reference computes, and each is held to the limit in the cell's file
(``bench/cells/<cell>.json``), set from the readings recorded in PERF.md:
nodes on another client; the relative gap of the training loss and the
RMS gap of the class logits over every node (the mean over the window's
jobs), both by the reference's forward at the program's final parameters
against at its own; the gap
between the norms of the two parameter changes; the widest gaps of the
per-round accuracy curves.

Leaf measures follow one rule: a leaf's gap is taken against the larger of
that leaf's own reference norm and the median leaf's, since some leaves
barely move; leaves whose first reference gradient is under a thousandth
of the median leaf's (a GAT layer's destination score vector under
softmax) move by round-off alone and are left out of the change.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

# Leaves whose mean first-step reference gradient norm is under this share
# of the median leaf's are nought to rounding: left out of the change.
GRAD_FLOOR = 1e-3
# Numbers whose window value is the mean over its jobs, not the worst job.
MEAN_OVER_JOBS = ("logit_rms_gap",)


def _leaves(tree) -> List[np.ndarray]:
    import jax

    return [np.asarray(x, np.float64) for x in jax.tree.leaves(tree)]


def _moving(grad_norms) -> np.ndarray:
    g = np.asarray([float(x) for x in _leaves(grad_norms)])
    return g >= GRAD_FLOOR * np.median(g)


def _change_gap(prog: List[np.ndarray], ref: List[np.ndarray],
                base: List[np.ndarray], keep: np.ndarray) -> float:
    """Gap between the norms of the program's and the reference's change
    from ``base``, worst over the kept leaves, each against the larger of
    that leaf's reference change and the median leaf's."""
    ref_change = np.array([np.linalg.norm(r - b) for r, b in zip(ref, base)])
    prog_change = np.array([np.linalg.norm(p - b) for p, b in zip(prog, base)])
    scale = np.maximum(ref_change, np.median(ref_change[keep]))
    return float((np.abs(prog_change - ref_change) / scale)[keep].max())


def _nll(logits: np.ndarray, labels: np.ndarray) -> np.ndarray:
    """Per-node cross-entropy, float64."""
    z = logits - logits.max(axis=1, keepdims=True)
    logp = z - np.log(np.exp(z).sum(axis=1, keepdims=True))
    return -logp[np.arange(labels.shape[0]), labels]


def numbers(result: Dict, ref: Dict, rounds: int) -> Dict[str, float]:
    """The compared numbers of one run.

    ``result`` is ``Trainer.run``'s dict; ``ref`` is
    ``bench.reference.federated.run`` over the same ``rounds``.
    """
    keep = _moving(ref["grad_norms"])
    base = _leaves(ref["params"][0])
    prog_final = _leaves(result["params"])
    ref_final = _leaves(ref["params"][rounds])
    change_gap = _change_gap(prog_final, ref_final, base, keep)
    val = np.abs(np.subtract(result["val_curve"], ref["val"][:rounds]))
    test = np.abs(np.subtract(result["test_curve"], ref["test"][:rounds]))
    owner = np.asarray(result["partition"].owner)
    z_ref = ref["logits"](ref["params"][rounds])
    z_prog = ref["logits"](result["params"])
    train = ref["train_mask"]
    loss_ref = _nll(z_ref, ref["labels"])[train].mean()
    loss_prog = _nll(z_prog, ref["labels"])[train].mean()
    return {
        "partition_moved": float(np.sum(owner != ref["owner"])),
        "loss_gap": float(abs(loss_prog - loss_ref) / loss_ref),
        "logit_rms_gap": float(np.sqrt(np.mean((z_prog - z_ref) ** 2) / np.mean(z_ref ** 2))),
        "change_gap": change_gap,
        "val_acc_gap": float(val.max()),
        "test_acc_gap": float(test.max()),
    }


def over_jobs(per_job: List[Dict[str, float]]) -> Dict[str, float]:
    """A window's numbers from its jobs' numbers: the worst job, except
    the logit gap, whose mean over the jobs is taken, since one job's
    reading swings with the trajectory while a precision loss shows in
    every job alike."""
    return {k: float(np.mean([n[k] for n in per_job])) if k in MEAN_OVER_JOBS
            else max(n[k] for n in per_job) for k in per_job[0]}


def judge(nums: Dict[str, float], limits: Dict[str, float]) -> Tuple[bool, List[str]]:
    """(all within limits, one 'name value limit' line per number). A
    number without a limit is printed and not held; a limit without a
    number fails."""
    ok = True
    lines = []
    for name in sorted(set(nums) | set(limits)):
        v = nums.get(name)
        lim = limits.get(name)
        if lim is None:
            lines.append(f"{name} {v!r} (not compared)")
            continue
        good = v is not None and np.isfinite(v) and v <= lim
        ok = ok and good
        lines.append(f"{name} {v!r} limit {lim!r}{'' if good else ' FAIL'}")
    return ok, lines
