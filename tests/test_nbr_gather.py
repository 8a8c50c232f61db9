"""repro.core.nbr_gather: the masked neighbour gather whose backward is a
gather over the graph's valid slots, checked against autodiff of the plain
masked gather and against the layers and client loss built without it."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.core import FedGATConfig
from repro.core.engine import get_engine
from repro.core.fedgat_model import init_params, layered_forward
from repro.core.gat import gat_layer_nbr, init_gat_layer, masked_cross_entropy
from repro.core.nbr_gather import NbrTable, nbr_gather, reverse_slots
from repro.core.poly_attention import edge_scores, head_projections
from repro.graphs.graph import make_graph_from_edges, pad_degree, sample_neighbors

D_IN, HEADS, HIDDEN, CLASSES = 6, 2, 4, 3


def _graph(idx, mask):
    return np.asarray(idx, np.int32), np.asarray(mask, bool)


def _capped(seed=1):
    """A star plus a ring, capped by ``sample_neighbors`` to rows of 3: the
    hub's in-degree far exceeds B = 4."""
    n = 24
    star = [(0, i) for i in range(1, n)]
    ring = [(i, i % (n - 1) + 1) for i in range(1, n)]
    rng = np.random.default_rng(0)
    g = make_graph_from_edges(
        rng.standard_normal((n, D_IN)), np.zeros(n, np.int32),
        np.asarray(star + ring), np.zeros(n, bool), np.zeros(n, bool),
        np.zeros(n, bool), CLASSES, pad_multiple=8,
    )
    g = sample_neighbors(g, max_degree=3, seed=seed, pad_multiple=4)
    return _graph(g.nbr_idx, g.nbr_mask)


def _duplicate():
    """Row 1 lists node 2 twice."""
    idx = [[0, 1, 0, 0], [1, 2, 2, 0], [2, 1, 0, 0], [3, 2, 0, 0], [4, 3, 0, 0]]
    mask = [[1, 1, 0, 0], [1, 1, 1, 0], [1, 1, 0, 0], [1, 1, 0, 0], [1, 1, 0, 0]]
    return _graph(idx, mask)


def _isolated():
    """Node 4 has only its self-loop, and no other row names it."""
    idx = [[0, 1, 0, 0], [1, 0, 2, 0], [2, 1, 3, 0], [3, 2, 0, 0], [4, 0, 0, 0]]
    mask = [[1, 1, 0, 0], [1, 1, 1, 0], [1, 1, 1, 0], [1, 1, 0, 0], [1, 0, 0, 0]]
    return _graph(idx, mask)


def _all_padding_row():
    """Row 2 is padding only: every slot names node 0 and none is valid."""
    idx = [[0, 1, 3, 0], [1, 0, 3, 0], [0, 0, 0, 0], [3, 1, 4, 0], [4, 3, 0, 0]]
    mask = [[1, 1, 1, 0], [1, 1, 1, 0], [0, 0, 0, 0], [1, 1, 1, 0], [1, 1, 0, 0]]
    return _graph(idx, mask)


GRAPHS = {
    "capped": _capped,
    "duplicate": _duplicate,
    "isolated": _isolated,
    "all_padding_row": _all_padding_row,
}

# The three gathers whose input carries a gradient: the gathered operand's
# shape after N, and the axis it is gathered on.
SITES = {
    "edge_scores_s2": ((HEADS,), ()),
    "gat_layer_s2": ((1,), ()),
    "gat_layer_z": ((1,), (CLASSES,)),
}


@pytest.fixture(params=sorted(GRAPHS))
def lists(request):
    return GRAPHS[request.param]()


def _table(idx, mask):
    rev, overflow = reverse_slots(idx, mask)
    return NbrTable(jnp.asarray(mask), jnp.asarray(rev), jnp.asarray(overflow))


def _plain(x, idx, mask):
    """The reference: autodiff's masked gather ``where(mask, x[idx], 0)``."""
    m = jnp.asarray(mask).reshape(mask.shape + (1,) * (x.ndim - 2))
    return jnp.where(m, x[:, idx], 0)


def _close(got, want, tol=1e-6):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    scale = max(1.0, float(np.abs(want).max(initial=0.0)))
    assert float(np.abs(got - want).max(initial=0.0)) <= tol * scale


# ---------------------------------------------------------------------------
# reverse_slots
# ---------------------------------------------------------------------------

def test_capped_graph_has_in_degree_above_b():
    idx, mask = _capped()
    assert np.bincount(idx[mask]).max() > idx.shape[1]


def _listed(rev, overflow, j, none):
    """The slots the tables list for node j, an overflow row read in place
    of its pointer."""
    out = []
    for s in rev[j]:
        if s > none:
            out += [t for t in overflow[s - none - 1] if t != none]
        elif s != none:
            out.append(s)
    return out


def test_reverse_slots_lists_each_valid_slot_once(lists):
    idx, mask = lists
    n, b = idx.shape
    rev, overflow = reverse_slots(idx, mask)
    in_degree = np.bincount(idx[mask], minlength=n)
    spilled = int((in_degree > b).sum())
    assert rev.dtype == overflow.dtype == np.int32
    assert rev.shape == (n, b)
    assert overflow.shape == (
        pad_degree(max(spilled, 1)), pad_degree(max(in_degree.max() - (b - 1), 1))
    )
    assert int((rev > n * b).sum()) == spilled
    listed = [_listed(rev, overflow, j, n * b) for j in range(n)]
    # Every valid slot exactly once, no padding slot, each under its node.
    assert sorted(sum(listed, [])) == np.flatnonzero(mask).tolist()
    for j, slots in enumerate(listed):
        assert len(slots) == in_degree[j]
        assert np.all(idx.reshape(-1)[slots] == j)


def test_reverse_slots_shape_comes_from_b_not_the_largest_in_degree():
    (idx1, mask1), (idx2, mask2) = _capped(seed=1), _capped(seed=2)
    deg1, deg2 = (np.bincount(i[m]).max() for i, m in ((idx1, mask1), (idx2, mask2)))
    assert deg1 != deg2
    shapes = [tuple(t.shape for t in reverse_slots(i, m)) for i, m in ((idx1, mask1), (idx2, mask2))]
    assert shapes[0] == shapes[1]


def test_reverse_slots_rejects_malformed_lists():
    idx, mask = _duplicate()
    with pytest.raises(ValueError, match="same"):
        reverse_slots(idx, mask[:, :2])
    bad = idx.copy()
    bad[1, 1] = idx.shape[0]
    with pytest.raises(ValueError, match="outside"):
        reverse_slots(bad, mask)


# ---------------------------------------------------------------------------
# The op against autodiff's masked gather
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("site", sorted(SITES))
def test_forward_and_vjp_match_masked_gather(lists, site):
    idx, mask = lists
    lead, trail = SITES[site]
    n = idx.shape[0]
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal(lead + (n,) + trail), jnp.float32)
    # A cotangent that is nonzero at padding slots too.
    ct = jnp.asarray(rng.standard_normal(lead + idx.shape + trail), jnp.float32)
    table = _table(idx, mask)
    op = lambda x: nbr_gather(x, jnp.asarray(idx), table, axis=1)
    ref = lambda x: _plain(x, idx, mask)

    out, vjp = jax.vjp(op, x)
    want, ref_vjp = jax.vjp(ref, x)
    assert np.array_equal(np.asarray(out), np.asarray(want))
    _close(vjp(ct)[0], ref_vjp(ct)[0])

    # Under jit, and under vmap over 3 lanes with the table unbatched.
    xs = jnp.stack([x, 2 * x, -x])
    cts = jnp.stack([ct, -ct, 0.5 * ct])
    lanes = jax.jit(jax.vmap(lambda x, c: jax.vjp(op, x)[1](c)[0]))
    ref_lanes = jax.vmap(lambda x, c: jax.vjp(ref, x)[1](c)[0])
    _close(lanes(xs, cts), ref_lanes(xs, cts))
    assert np.array_equal(
        np.asarray(jax.jit(jax.vmap(op))(xs)), np.asarray(jax.vmap(ref)(xs))
    )


def _in_slot_order(ct_x, ct, idx, mask, rev):
    """x's gradient as the op documents it, in NumPy: x's other cotangent,
    then each valid slot naming the node in ascending order; a node whose
    row spills adds its first B - 1 slots, then the sum of the rest."""
    n, b = idx.shape
    flat = ct.reshape(ct.shape[0], n * b, *ct.shape[3:])
    out = np.array(ct_x)
    for j in range(n):
        slots = [int(s) for s in np.flatnonzero(mask.reshape(-1)) if idx.reshape(-1)[s] == j]
        spills = rev[j, b - 1] > n * b
        for s in slots[: b - 1] if spills else slots:
            out[:, j] += flat[:, s]
        if spills:
            rest = flat[:, slots[b - 1]]
            for s in slots[b:]:
                rest = rest + flat[:, s]
            out[:, j] += rest
    return out


@pytest.mark.parametrize("site", sorted(SITES))
def test_with_x_adds_the_slots_onto_the_other_cotangent(lists, site):
    """``with_x`` returns x for its other uses: x's gradient is their
    cotangent plus each slot's, in the order a scatter-add takes."""
    idx, mask = lists
    lead, trail = SITES[site]
    n = idx.shape[0]
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal(lead + (n,) + trail), jnp.float32)
    ct_x = rng.standard_normal(x.shape).astype(np.float32)
    ct = rng.standard_normal(lead + idx.shape + trail).astype(np.float32)
    table = _table(idx, mask)
    op = lambda x: nbr_gather(x, jnp.asarray(idx), table, axis=1, with_x=True)
    ref = lambda x: (x, _plain(x, idx, mask))

    same, out = op(x)
    assert np.array_equal(np.asarray(same), np.asarray(x))
    assert np.array_equal(np.asarray(out), np.asarray(ref(x)[1]))
    got = jax.jit(lambda c0, c1: jax.vjp(op, x)[1]((c0, c1))[0])(ct_x, ct)
    _close(got, jax.vjp(ref, x)[1]((jnp.asarray(ct_x), jnp.asarray(ct)))[0])
    assert np.array_equal(np.asarray(got), _in_slot_order(ct_x, ct, idx, mask, np.asarray(table.rev)))


# ---------------------------------------------------------------------------
# The layers and the client loss against the code without a table
# ---------------------------------------------------------------------------

def _features(n, seed=5):
    return jnp.asarray(np.random.default_rng(seed).standard_normal((n, D_IN)), jnp.float32)


def _client_mask(mask):
    """A client's view: a subset of the graph's valid slots."""
    keep = np.random.default_rng(7).random(mask.shape) < 0.7
    keep[:, 0] = True
    return jnp.asarray(mask & keep)


@pytest.mark.parametrize("layer_name", ["edge_scores", "gat_layer_nbr"])
def test_layer_outputs_and_gradients_match(lists, layer_name):
    """``edge_scores`` gathers s2; ``gat_layer_nbr`` gathers s2 and z."""
    idx, mask = lists
    n = idx.shape[0]
    h = _features(n)
    nbr_idx, view, table = jnp.asarray(idx), _client_mask(mask), _table(idx, mask)
    layer = init_gat_layer(jax.random.PRNGKey(0), D_IN, HIDDEN, HEADS)

    if layer_name == "edge_scores":
        def out(p, t):
            b1, b2 = head_projections(p)
            return jnp.where(view[None], edge_scores(b1, b2, h, nbr_idx, t), 0)
    else:
        def out(p, t):
            return gat_layer_nbr(p, h, nbr_idx, view, concat=True, table=t)

    _close(out(layer, table), out(layer, None))
    weights = jnp.asarray(np.random.default_rng(9).standard_normal(out(layer, None).shape))
    grad = lambda t: jax.grad(lambda p: jnp.sum(weights * out(p, t)))(layer)
    for got, want in zip(jax.tree.leaves(grad(table)), jax.tree.leaves(grad(None))):
        _close(got, want)


def _loss_fn(engine, coeffs, h, idx, train):
    def loss(params, view, table):
        logits = layered_forward(engine, params, coeffs, None, h, idx, view, table)
        return masked_cross_entropy(logits, jnp.zeros(h.shape[0], jnp.int32), train)

    return loss


@pytest.mark.parametrize("engine_name", ["kernel", "direct", "exact"])
def test_client_loss_gradient_matches(lists, engine_name):
    idx, mask = lists
    n = idx.shape[0]
    cfg = FedGATConfig(engine=engine_name, degree=4, hidden=HIDDEN, heads=HEADS, out_heads=2)
    engine = get_engine(engine_name)(cfg)
    coeffs = jnp.asarray(cfg.coeffs(), jnp.float32)
    h = _features(n)
    params = init_params(jax.random.PRNGKey(1), D_IN, CLASSES, cfg)
    train = jnp.ones(n, bool)
    loss = _loss_fn(engine, coeffs, h, jnp.asarray(idx), train)
    view, table = _client_mask(mask), _table(idx, mask)

    want_val, want = jax.value_and_grad(loss)(params, view, None)
    got_val, got = jax.jit(jax.value_and_grad(loss))(params, view, table)
    _close(got_val, want_val)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _close(a, b)

    # 3 lanes of per-client params, the table and the graph unbatched.
    lanes = jax.tree.map(lambda x: jnp.stack([x, 0.9 * x, 1.1 * x]), params)
    views = jnp.stack([view, jnp.asarray(mask), view])
    batched = lambda t: jax.vmap(jax.grad(loss), in_axes=(0, 0, None))(lanes, views, t)
    for a, b in zip(jax.tree.leaves(jax.jit(batched)(table)), jax.tree.leaves(batched(None))):
        _close(a, b)


# ---------------------------------------------------------------------------
# The compiled gradient and the counters
# ---------------------------------------------------------------------------

def _kernel_loss_text(table_on: bool) -> str:
    idx, mask = _capped()
    n = idx.shape[0]
    cfg = FedGATConfig(engine="kernel", degree=4, hidden=HIDDEN, heads=HEADS)
    engine = get_engine("kernel")(cfg)
    coeffs = jnp.asarray(cfg.coeffs(), jnp.float32)
    params = init_params(jax.random.PRNGKey(1), D_IN, CLASSES, cfg)
    loss = _loss_fn(engine, coeffs, _features(n), jnp.asarray(idx), jnp.ones(n, bool))
    table = _table(idx, mask) if table_on else None
    return jax.jit(jax.grad(loss)).lower(params, jnp.asarray(mask), table).compile().as_text()


def _scatters_under_nbr_gather(text):
    return [
        line for line in text.splitlines()
        if re.search(r"= \S+ scatter\(", line)
        and "nbr_gather" in (re.search(r'op_name="([^"]*)"', line) or [""])[0]
    ]


@pytest.mark.parametrize("table_on", [True, False], ids=["table", "no_table"])
def test_compiled_gradient_scatters_only_without_table(table_on):
    scatters = _scatters_under_nbr_gather(_kernel_loss_text(table_on))
    assert bool(scatters) is not table_on, scatters


@pytest.mark.parametrize("table_on", [True, False], ids=["table", "no_table"])
def test_counters_name_the_backward_built(table_on):
    gather = telemetry.counter("nbr_gather.gather_vjp")
    scatter = telemetry.counter("nbr_gather.scatter_vjp")
    before = gather.value, scatter.value
    idx, mask = _duplicate()
    layer = init_gat_layer(jax.random.PRNGKey(0), D_IN, HIDDEN, HEADS)
    table = _table(idx, mask) if table_on else None
    jax.make_jaxpr(lambda p: gat_layer_nbr(
        p, _features(idx.shape[0]), jnp.asarray(idx), jnp.asarray(mask), True, table=table
    ))(layer)
    # gat_layer_nbr gathers twice: the neighbour scores and the embeddings.
    want = (2, 0) if table_on else (0, 2)
    assert (gather.value - before[0], scatter.value - before[1]) == want


@pytest.mark.parametrize(
    "method,backend,lanes",
    [("fedgat", "vmap", 2), ("fedgat", "vmap", None), ("distgat", "shard_map", None)],
    ids=["cohort", "legacy_vmap", "shard_map_distgat"],
)
def test_every_training_backend_builds_the_gather_backward(method, backend, lanes):
    from repro.federated import FederatedConfig, Trainer
    from repro.graphs import make_cora_like

    gather = telemetry.counter("nbr_gather.gather_vjp")
    scatter = telemetry.counter("nbr_gather.scatter_vjp")
    before = gather.value, scatter.value
    cfg = FederatedConfig(
        method=method, backend=backend, num_clients=2, rounds=1, local_steps=1,
        max_concurrent_clients=lanes, model=FedGATConfig(engine="direct", degree=4),
    )
    Trainer(cfg).run(make_cora_like("tiny", seed=0))
    assert gather.value > before[0]
    assert scatter.value == before[1]
