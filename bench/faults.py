"""Faults planted under the timed path, to show that the comparison sees
them (``bench/tests/test_faults.py`` on the CPU, ``bench/readings.py`` on
the chip at a cell's own size). Each patches one function of the program
for the duration of a ``with planted(name):`` block:

  state_unchanged    every client's local update returns the global model
                     and its optimizer state unchanged;
  half_batch         each client's loss is the mean over the first half of
                     its training nodes, the rest left out;
  aggregate_altered  the cohort fold counts the first lane's model twice,
                     so the round's FedAvg answer is altered where it is
                     produced.

(The fourth fault of a training cell, the exchange between chips left out,
exists only in a cell on several chips; no cell here has one.)
"""
from __future__ import annotations

from contextlib import contextmanager

FAULTS = ("state_unchanged", "half_batch", "aggregate_altered")


def _unchanged_local_update(make_local_update):
    def build(loss_fn, cfg):
        def local_update(gparams, opt_state, data, nb_mask, tr_mask, noise_key):
            return gparams, opt_state

        return local_update

    return build


def _half_batch_loss(masked_cross_entropy):
    import jax.numpy as jnp

    def loss(logits, labels, mask):
        m = mask.astype(jnp.int32)
        keep = (jnp.cumsum(m) <= (jnp.sum(m) + 1) // 2) & (m > 0)
        return masked_cross_entropy(logits, labels, keep)

    return loss


def _doubled_first_lane(running_update):
    import jax.numpy as jnp

    def update(state, stacked_params, weights, scale=1.0):
        w = jnp.asarray(weights, jnp.float32)
        w = w.at[0].multiply(2.0)
        return running_update(state, stacked_params, w, scale)

    return update


_PATCHES = {
    "state_unchanged": ("repro.federated.trainer", "make_local_update", _unchanged_local_update),
    "half_batch": ("repro.federated.trainer", "masked_cross_entropy", _half_batch_loss),
    "aggregate_altered": ("repro.federated.cohort", "running_update", _doubled_first_lane),
}


@contextmanager
def planted(name: str):
    import importlib

    module_name, attr, make = _PATCHES[name]
    module = importlib.import_module(module_name)
    original = getattr(module, attr)
    setattr(module, attr, make(original))
    try:
        yield
    finally:
        setattr(module, attr, original)
