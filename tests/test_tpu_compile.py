"""Compile the kernel path for a described TPU v5e chip, with no chip attached.

The TPU compiler ships with jaxlib's TPU plugin and compiles for a topology
that is described rather than attached. These tests lower the fused
``cheb_attn`` kernel the way the ``kernel`` engine calls it, with
``interpret=False`` and the tiles ``select_block_sizes`` picks for the
compiled path, and require a ``tpu_custom_call`` in the compiled program: a
kernel quietly replaced by its jnp reference fails here. Nothing runs, so
these say nothing about results or speed.

The topology is described inside a module fixture, never at import time: the
TPU library admits one process at a time, and every test worker imports this
file.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.fedgat_model import FedGATConfig
from repro.kernels.cheb_attn import cheb_attn_diff
from repro.kernels.ops import cheb_attn_layer, clear_block_cache, select_block_sizes

COEFFS = np.asarray(FedGATConfig().coeffs(), np.float32)
HIDDEN = 8

# (N, B, d, H): sbm_100k, cora_like (the CLIs' default dataset), and the
# width of Planetoid Cora (1,433 bag-of-words features).
SBM_100K = (100_000, 16, 32, 8)
LAYER_SHAPES = [SBM_100K, (320, 16, 48, 8), (2708, 32, 1433, 8)]


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    clear_block_cache()
    yield SingleDeviceSharding(topo.devices[0])
    clear_block_cache()
    jax.config.update("jax_enable_compilation_cache", was)


def _layer_args(sharding, n, b, d, heads, lanes=None):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    lead = () if lanes is None else (lanes,)
    params = {
        "W": sds(lead + (heads, d, HIDDEN), jnp.float32),
        "a1": sds(lead + (heads, HIDDEN), jnp.float32),
        "a2": sds(lead + (heads, HIDDEN), jnp.float32),
    }
    graph = (
        sds((len(COEFFS),), jnp.float32),
        sds((n, d), jnp.float32),
        sds((n, b), jnp.int32),
        sds((n, b), jnp.bool_),
    )
    return params, graph


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("shape", LAYER_SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_cheb_attn_forward_compiles_for_v5e(one_chip, shape):
    n, b, d, heads = shape
    bn, bd = select_block_sizes(n, b, d, heads=heads, interpret=False)
    assert bn % 8 == 0 and (bd % 128 == 0 or bd == d), (bn, bd)
    params, graph = _layer_args(one_chip, *shape)
    text = _compiled_text(partial(cheb_attn_layer, interpret=False), params, *graph)
    assert "tpu_custom_call" in text


def test_cheb_attn_diff_value_and_grad_compiles_for_v5e(one_chip):
    n, b, d, heads = SBM_100K
    bn, bd = select_block_sizes(n, b, d, heads=heads, interpret=False)
    n = -(-n // bn) * bn      # cheb_attn_layer pads N to the block multiple
    sds = partial(jax.ShapeDtypeStruct, sharding=one_chip)
    args = (
        sds((heads, n, b), jnp.float32),
        sds((n, b, d), jnp.float32),
        sds((n, b), jnp.float32),
        sds((len(COEFFS),), jnp.float32),
    )

    def loss(x, h_nb, mask, coeffs):
        # The value is returned, so the forward pallas_call stays live: a
        # bare jax.grad would let the compiler drop it.
        return cheb_attn_diff(x, h_nb, mask, coeffs, bn, bd, False).sum()

    text = _compiled_text(jax.value_and_grad(loss), *args)
    assert "tpu_custom_call" in text


def test_cheb_attn_vmapped_over_clients_compiles_for_v5e(one_chip):
    """Two clients' local steps, batched as the vmap cohort step batches
    them: per-lane params, one shared graph."""
    params, graph = _layer_args(one_chip, *SBM_100K, lanes=2)

    def client_loss(p, coeffs, h, nbr_idx, nbr_mask):
        return cheb_attn_layer(p, coeffs, h, nbr_idx, nbr_mask, interpret=False).sum()

    step = jax.vmap(
        jax.value_and_grad(client_loss), in_axes=(0, None, None, None, None)
    )
    text = _compiled_text(step, params, *graph)
    assert "tpu_custom_call" in text


def test_kernel_keeps_its_name_under_the_layer_scope(one_chip, monkeypatch):
    """Trained through the model, the kernel runs under the ``layer1`` named
    scope; its custom call must still carry ``cheb_attn`` in its HLO name,
    the name a profile's device ops are matched on."""
    import re

    from repro.core.engine import get_engine
    from repro.core.fedgat_model import layered_forward

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    n, b, d, heads = 320, 16, 48, 8
    cfg = FedGATConfig(engine="kernel", heads=heads, hidden=HIDDEN)
    engine = get_engine("kernel")(cfg)
    layer1, graph = _layer_args(one_chip, n, b, d, heads)
    layer2 = {
        "W": jax.ShapeDtypeStruct((1, heads * HIDDEN, 3), jnp.float32, sharding=one_chip),
        "a1": jax.ShapeDtypeStruct((1, 3), jnp.float32, sharding=one_chip),
        "a2": jax.ShapeDtypeStruct((1, 3), jnp.float32, sharding=one_chip),
    }

    def loss(params, coeffs, h, nbr_idx, nbr_mask):
        return layered_forward(engine, params, coeffs, None, h, nbr_idx, nbr_mask).sum()

    text = _compiled_text(jax.value_and_grad(loss), [layer1, layer2], *graph)
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert calls
    for line in calls:
        assert "cheb_attn" in line.split(" = ", 1)[0], line[:200]
        assert re.search(r'op_name="[^"]*layer1[^"]*"', line), line[:200]


def test_neighbour_gather_backward_has_no_scatter_on_v5e(one_chip, monkeypatch):
    """Compiled for the chip, the kernel engine's client gradient with the
    graph's reverse-slot tables keeps no scatter under ``nbr_gather``."""
    import re

    from repro.core.engine import get_engine
    from repro.core.fedgat_model import layered_forward
    from repro.core.nbr_gather import NbrTable, reverse_slots

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    n, b, d, heads = 320, 16, 48, 8
    rng = np.random.default_rng(0)
    nbr_idx = rng.integers(0, n, (n, b)).astype(np.int32)
    nbr_mask = rng.random((n, b)) < 0.4
    rev, overflow = reverse_slots(nbr_idx, nbr_mask)
    cfg = FedGATConfig(engine="kernel", heads=heads, hidden=HIDDEN, out_heads=heads)
    engine = get_engine("kernel")(cfg)
    layer1, graph = _layer_args(one_chip, n, b, d, heads)
    sds = partial(jax.ShapeDtypeStruct, sharding=one_chip)
    layer2 = {
        "W": sds((heads, heads * HIDDEN, 3), jnp.float32),
        "a1": sds((heads, 3), jnp.float32),
        "a2": sds((heads, 3), jnp.float32),
    }
    table = NbrTable(sds((n, b), jnp.bool_), sds(rev.shape, jnp.int32), sds(overflow.shape, jnp.int32))

    def loss(params, coeffs, h, nbr_idx, nbr_mask, table):
        return layered_forward(engine, params, coeffs, None, h, nbr_idx, nbr_mask, table).sum()

    text = _compiled_text(jax.grad(loss), [layer1, layer2], *graph, table)
    names = [re.search(r'op_name="([^"]*)"', line) for line in text.splitlines()
             if re.search(r"= \S+ scatter\(", line)]
    assert not [m.group(1) for m in names if m and "nbr_gather" in m.group(1)]
    assert re.search(r'op_name="[^"]*transpose\(jvp\(layer2\)\)/nbr_gather/[^"]*gather', text)
