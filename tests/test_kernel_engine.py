"""Head-batched cheb_attn kernel + "kernel" engine through the Trainer.

Covers the masked paths (isolated node -> exact zero row, never NaN), head
counts H in {1, 4, 8} against the per-head oracle, odd-N/D layer padding,
the block-size autotuner, gradients through the custom_vjp, and
kernel-vs-direct engine parity inside short federated runs on BOTH
backends (shard_map in a subprocess: forced device count must precede jax
init)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FedGATConfig, fedgat_forward, init_params
from repro.core.chebyshev import attention_series
from repro.core.poly_attention import poly_gat_layer
from repro.kernels import (
    cheb_attn,
    cheb_attn_diff,
    clear_block_cache,
    ref,
    select_block_sizes,
)
from repro.kernels.ops import cheb_attn_layer

ATT16 = jnp.asarray(attention_series(16, (-4.0, 4.0)), jnp.float32)


def _rand_scores(key, shape):
    return jnp.clip(jax.random.normal(key, shape), -3.5, 3.5)


# ---------------------------------------------------------------------------
# masked paths: isolated nodes
# ---------------------------------------------------------------------------

def test_isolated_rows_exact_zero_no_nan():
    n, b, d, H = 24, 8, 16, 4
    x = _rand_scores(jax.random.PRNGKey(0), (H, n, b))
    h = jax.random.normal(jax.random.PRNGKey(1), (n, b, d))
    m = jnp.ones((n, b)).at[3].set(0.0).at[17].set(0.0)   # two isolated nodes
    out = cheb_attn(x, h, m, ATT16, block_n=8, block_d=8)
    assert not bool(jnp.isnan(out).any())
    assert bool((out[:, 3] == 0.0).all()) and bool((out[:, 17] == 0.0).all())
    # the oracle agrees (same guarded semantics)
    want = ref.cheb_attn_ref(x, h, m, ATT16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


def test_all_isolated_graph_is_all_zero():
    n, b, d = 16, 8, 8
    x = _rand_scores(jax.random.PRNGKey(2), (n, b))
    h = jax.random.normal(jax.random.PRNGKey(3), (n, b, d))
    out = cheb_attn(x, h, jnp.zeros((n, b)), ATT16, block_n=8, block_d=8)
    assert bool((out == 0.0).all())


def test_direct_engine_isolated_node_matches_kernel():
    """The direct oracle applies the same den != 0 guard: a degree-0 node
    aggregates to zero on BOTH engines (no NaN divergence between them)."""
    n, d, B, H, o = 16, 8, 8, 2, 4
    h = jax.random.normal(jax.random.PRNGKey(40), (n, d))
    nbr_idx = jax.random.randint(jax.random.PRNGKey(41), (n, B), 0, n)
    nbr_mask = jnp.ones((n, B), bool).at[5].set(False)    # node 5 isolated
    params = {
        "W": jax.random.normal(jax.random.PRNGKey(42), (H, d, o)) * 0.2,
        "a1": jax.random.normal(jax.random.PRNGKey(43), (H, o)) * 0.2,
        "a2": jax.random.normal(jax.random.PRNGKey(44), (H, o)) * 0.2,
    }
    out_d = poly_gat_layer(params, ATT16, h, nbr_idx, nbr_mask)
    out_k = cheb_attn_layer(params, ATT16, h, nbr_idx, nbr_mask)
    assert not bool(jnp.isnan(out_d).any())
    np.testing.assert_array_equal(np.asarray(out_d[5]), 0.0)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_d),
                               rtol=1e-4, atol=1e-5)


def test_all_engines_isolated_node_zero():
    """Every series engine (matrix/vector/direct/kernel) aggregates a
    degree-0 node to exact zeros — no engine NaNs and they stay in parity."""
    from repro.core import make_pack
    from repro.graphs import make_cora_like

    g = make_cora_like("tiny", seed=0)
    h = jnp.asarray(g.features)
    nbr_idx = jnp.asarray(g.nbr_idx)
    nbr_mask = jnp.asarray(g.nbr_mask).at[5].set(False)   # isolate node 5
    outs = {}
    for engine in ("matrix", "vector", "direct", "kernel"):
        cfg = FedGATConfig(degree=10, engine=engine)
        coeffs = jnp.asarray(cfg.coeffs(), jnp.float32)
        params = init_params(jax.random.PRNGKey(1), g.feature_dim, g.num_classes, cfg)
        pack = make_pack(jax.random.PRNGKey(2), cfg, h, nbr_idx, nbr_mask)
        outs[engine] = np.asarray(
            fedgat_forward(params, cfg, coeffs, pack, h, nbr_idx, nbr_mask)
        )
        assert not np.isnan(outs[engine]).any(), engine
    for engine in ("matrix", "vector", "kernel"):
        np.testing.assert_allclose(outs[engine], outs["direct"],
                                   rtol=1e-3, atol=1e-4, err_msg=engine)


def test_isolated_node_zero_through_layer():
    """Layer level: a fully-masked neighbour list aggregates to zero before
    the W projection (the old path NaN'd here and needed fake neighbours)."""
    n, d, B, H, o = 20, 12, 8, 4, 6
    key = jax.random.PRNGKey(4)
    h = jax.random.normal(key, (n, d))
    nbr_idx = jax.random.randint(jax.random.PRNGKey(5), (n, B), 0, n)
    nbr_mask = jnp.ones((n, B), bool).at[7].set(False)    # node 7 isolated
    params = {
        "W": jax.random.normal(jax.random.PRNGKey(6), (H, d, o)) * 0.2,
        "a1": jax.random.normal(jax.random.PRNGKey(7), (H, o)) * 0.2,
        "a2": jax.random.normal(jax.random.PRNGKey(8), (H, o)) * 0.2,
    }
    out = cheb_attn_layer(params, ATT16, h, nbr_idx, nbr_mask)
    assert not bool(jnp.isnan(out).any())
    np.testing.assert_array_equal(np.asarray(out[7]), 0.0)


# ---------------------------------------------------------------------------
# head-batched parity vs the per-head oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("H", [1, 4, 8])
def test_head_batched_parity(H):
    """One pallas_call for all H heads: <= 1e-5 per head vs cheb_attn_ref,
    with isolated rows in the mix coming out as exact zeros."""
    n, b, d = 32, 16, 32
    x = _rand_scores(jax.random.PRNGKey(H), (H, n, b))
    h = jax.random.normal(jax.random.PRNGKey(H + 1), (n, b, d))
    m = jnp.ones((n, b)).at[6].set(0.0).at[21].set(0.0)
    out = cheb_attn(x, h, m, ATT16, block_n=16, block_d=32)
    assert out.shape == (H, n, d)
    assert bool((out[:, 6] == 0.0).all()) and bool((out[:, 21] == 0.0).all())
    for i in range(H):
        want = ref.cheb_attn_ref(x[i], h, m, ATT16)
        assert float(jnp.abs(out[i] - want).max()) <= 1e-5

    # masked neighbour lists at looser (conditioning-limited) tolerance
    mb = jax.random.bernoulli(jax.random.PRNGKey(H + 2), 0.7, (n, b))
    mb = mb.at[:, 0].set(True).astype(jnp.float32)
    out_b = cheb_attn(x, h, mb, ATT16, block_n=16, block_d=32)
    want_b = ref.cheb_attn_ref(x, h, mb, ATT16)
    np.testing.assert_allclose(np.asarray(out_b), np.asarray(want_b),
                               rtol=1e-4, atol=5e-5)


def test_multi_graph_batch_parity():
    """The optional leading graph-batch dim: (G, H, N, B) in one call."""
    G, H, n, b, d = 3, 2, 16, 8, 16
    x = _rand_scores(jax.random.PRNGKey(9), (G, H, n, b))
    h = jax.random.normal(jax.random.PRNGKey(10), (G, n, b, d))
    m = jax.random.bernoulli(jax.random.PRNGKey(11), 0.8, (G, n, b))
    m = m.at[:, :, 0].set(True).astype(jnp.float32)
    out = cheb_attn(x, h, m, ATT16, block_n=8, block_d=8)
    assert out.shape == (G, H, n, d)
    for g in range(G):
        for i in range(H):
            want = ref.cheb_attn_ref(x[g, i], h[g], m[g], ATT16)
            assert float(jnp.abs(out[g, i] - want).max()) <= 1e-5


@pytest.mark.parametrize("n,d", [(13, 10), (50, 22), (127, 129)])
def test_layer_odd_shapes_pad_and_match_direct(n, d):
    """Odd N/D: the layer pads to block multiples and still matches the
    direct oracle exactly on the unpadded region."""
    B, H, o = 8, 4, 6
    h = jax.random.normal(jax.random.PRNGKey(n), (n, d))
    nbr_idx = jax.random.randint(jax.random.PRNGKey(n + 1), (n, B), 0, n)
    nbr_mask = jax.random.bernoulli(jax.random.PRNGKey(n + 2), 0.6, (n, B))
    nbr_mask = nbr_mask.at[:, 0].set(True)
    params = {
        "W": jax.random.normal(jax.random.PRNGKey(d), (H, d, o)) * 0.2,
        "a1": jax.random.normal(jax.random.PRNGKey(d + 1), (H, o)) * 0.2,
        "a2": jax.random.normal(jax.random.PRNGKey(d + 2), (H, o)) * 0.2,
    }
    out_k = cheb_attn_layer(params, ATT16, h, nbr_idx, nbr_mask)
    out_d = poly_gat_layer(params, ATT16, h, nbr_idx, nbr_mask)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_d),
                               rtol=1e-4, atol=1e-5)


def test_layer_honours_block_args():
    """Explicit block sizes are honoured (no hardcoded bn=8) and agree with
    the autotuned call."""
    n, d, B, H, o = 32, 16, 8, 2, 4
    h = jax.random.normal(jax.random.PRNGKey(20), (n, d))
    nbr_idx = jax.random.randint(jax.random.PRNGKey(21), (n, B), 0, n)
    nbr_mask = jnp.ones((n, B), bool)
    params = {
        "W": jax.random.normal(jax.random.PRNGKey(22), (H, d, o)) * 0.2,
        "a1": jax.random.normal(jax.random.PRNGKey(23), (H, o)) * 0.2,
        "a2": jax.random.normal(jax.random.PRNGKey(24), (H, o)) * 0.2,
    }
    auto = cheb_attn_layer(params, ATT16, h, nbr_idx, nbr_mask)
    for bn, bd in ((8, 8), (16, 16), (32, 8), (64, 128)):
        got = cheb_attn_layer(params, ATT16, h, nbr_idx, nbr_mask,
                              block_n=bn, block_d=bd)
        np.testing.assert_allclose(np.asarray(got), np.asarray(auto),
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# autotune selector
# ---------------------------------------------------------------------------

def test_select_block_sizes_candidates_and_memo():
    clear_block_cache()
    bn, bd = select_block_sizes(320, 32, 48, heads=8, interpret=True)
    assert bn in (128, 64, 32, 16, 8) and bd in (128, 64, 32, 16, 8)
    # memoised: same key -> same (cached) answer
    assert select_block_sizes(320, 32, 48, heads=8, interpret=True) == (bn, bd)
    # interpret mode weighs grid steps heavily -> never finer than compiled
    cn, cd = select_block_sizes(320, 32, 48, heads=8, interpret=False)
    assert bn * bd >= cn * cd


def test_select_block_sizes_env_override(monkeypatch):
    clear_block_cache()
    monkeypatch.setenv("REPRO_CHEB_BLOCK_N", "16")
    monkeypatch.setenv("REPRO_CHEB_BLOCK_D", "8")
    assert select_block_sizes(512, 32, 128, interpret=True) == (16, 8)
    monkeypatch.delenv("REPRO_CHEB_BLOCK_N")
    monkeypatch.delenv("REPRO_CHEB_BLOCK_D")
    bn, bd = select_block_sizes(512, 32, 128, interpret=True)
    assert (bn, bd) != (16, 8)  # override not baked into the memo


def test_select_block_sizes_degenerate_degree_falls_back():
    # B so large even the smallest (8, 8) tile blows the VMEM budget: the
    # selector refuses with the shape named instead of handing the kernel
    # a tile that cannot fit.
    clear_block_cache()
    with pytest.raises(ValueError, match="N=64, B=20000, d=32"):
        select_block_sizes(64, 20_000, 32, interpret=True)


@pytest.mark.parametrize("bad", ["0", "-8", "128k"])
def test_select_block_sizes_env_validation(monkeypatch, bad):
    clear_block_cache()
    monkeypatch.setenv("REPRO_CHEB_BLOCK_N", bad)
    with pytest.raises(ValueError, match="REPRO_CHEB_BLOCK_N"):
        select_block_sizes(64, 8, 32, interpret=True)


def test_select_block_sizes_respects_vmem_budget():
    # large padded degree: the h tile (bn*b*bd*4 bytes) must stay under the
    # budget, forcing small tiles rather than an OOM-sized block, and the
    # tile stays one Mosaic lowers (bn % 8, bd % 128 or bd == d)
    bn, bd = select_block_sizes(4096, 512, 4096, interpret=False)
    assert 4 * bn * 512 * bd <= 4 * 1024 * 1024
    assert bn % 8 == 0 and (bd % 128 == 0 or bd == 4096)
    # no legal tile at all: refuse, naming the shape
    with pytest.raises(ValueError, match="N=4096, B=2048, d=4096"):
        select_block_sizes(4096, 2048, 4096, interpret=False)


# ---------------------------------------------------------------------------
# gradients through the kernel engine (custom_vjp)
# ---------------------------------------------------------------------------

def test_grad_through_kernel_matches_oracle():
    n, b, d, H = 16, 8, 16, 4
    x = _rand_scores(jax.random.PRNGKey(30), (H, n, b))
    h = jax.random.normal(jax.random.PRNGKey(31), (n, b, d))
    m = jnp.ones((n, b)).at[5].set(0.0)                   # isolated node too

    def f_kernel(x_):
        return (cheb_attn_diff(x_, h, m, ATT16, 8, 8, True) ** 2).sum()

    def f_ref(x_):
        return (ref.cheb_attn_ref(x_, h, m, ATT16) ** 2).sum()

    g_k = jax.grad(f_kernel)(x)
    g_r = jax.grad(f_ref)(x)
    assert not bool(jnp.isnan(g_k).any())
    np.testing.assert_allclose(np.asarray(g_k), np.asarray(g_r),
                               rtol=1e-3, atol=1e-4)
    # isolated rows contribute zero gradient
    np.testing.assert_array_equal(np.asarray(g_k[:, 5]), 0.0)


def test_kernel_engine_grads_match_direct():
    from repro.graphs import make_cora_like

    g = make_cora_like("tiny", seed=0)
    h = jnp.asarray(g.features)
    nbr_idx = jnp.asarray(g.nbr_idx)
    nbr_mask = jnp.asarray(g.nbr_mask)

    def grads(engine):
        cfg = FedGATConfig(degree=10, engine=engine)
        coeffs = jnp.asarray(cfg.coeffs(), jnp.float32)
        params = init_params(jax.random.PRNGKey(1), g.feature_dim, g.num_classes, cfg)

        def fn(p):
            out = fedgat_forward(p, cfg, coeffs, None, h, nbr_idx, nbr_mask)
            return jnp.sum(out ** 2)

        return jax.grad(fn)(params)

    g_d = grads("direct")
    g_k = grads("kernel")
    for a, b in zip(jax.tree.leaves(g_d), jax.tree.leaves(g_k)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-3, atol=5e-4)


# ---------------------------------------------------------------------------
# kernel engine == direct engine inside the federated Trainer
# ---------------------------------------------------------------------------

def test_kernel_engine_federated_parity_vmap():
    """A short fedgat run with engine='kernel' reproduces engine='direct'
    metrics exactly on the vmap backend."""
    from repro.federated import FederatedConfig, run_federated
    from repro.graphs import make_cora_like

    g = make_cora_like("tiny", seed=0)

    def run(engine):
        cfg = FederatedConfig(
            method="fedgat", num_clients=4, rounds=3, local_steps=2,
            model=FedGATConfig(engine=engine, degree=10),
        )
        return run_federated(g, cfg)

    r_d = run("direct")
    r_k = run("kernel")
    np.testing.assert_allclose(r_k["test_curve"], r_d["test_curve"], atol=1e-6)
    np.testing.assert_allclose(r_k["val_curve"], r_d["val_curve"], atol=1e-6)
    assert abs(r_k["best_test"] - r_d["best_test"]) < 1e-6


SHARDED_KERNEL_SCRIPT = r"""
import numpy as np, jax
assert len(jax.devices()) == 2, jax.devices()
from repro.graphs import make_cora_like
from repro.federated import FederatedConfig, run_federated
from repro.core import FedGATConfig

g = make_cora_like('tiny', 0)
res = {}
for engine in ('direct', 'kernel'):
    cfg = FederatedConfig(method='fedgat', num_clients=2, rounds=3,
                          local_steps=1,
                          model=FedGATConfig(engine=engine, degree=10))
    res[engine] = run_federated(g, cfg, backend='shard_map')
np.testing.assert_allclose(res['kernel']['test_curve'],
                           res['direct']['test_curve'], atol=1e-6)
np.testing.assert_allclose(res['kernel']['val_curve'],
                           res['direct']['val_curve'], atol=1e-6)
assert res['kernel']['backend'] == 'shard_map'
print('KERNEL_SHARDED_OK')
"""


def test_kernel_engine_federated_parity_shard_map():
    """engine='kernel' completes a shard_map run matching engine='direct'
    (subprocess: forced device count must precede jax init)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", SHARDED_KERNEL_SCRIPT], env=env,
        capture_output=True, text=True, timeout=580,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "KERNEL_SHARDED_OK" in out.stdout
