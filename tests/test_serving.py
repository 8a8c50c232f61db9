"""repro.serving: pack cache, incremental updates, checkpoint round-trip,
microbatching scheduler, and the serve benchmark contract."""
import dataclasses
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FedGAT, FedGATConfig
from repro.federated.partition import client_neighbor_masks, dirichlet_partition
from repro.federated.trainer import FederatedConfig, Trainer
from repro.graphs import make_cora_like
from repro.serving import (
    GraphDelta,
    GraphInferenceServer,
    MicroBatcher,
    PackCache,
    PackEntry,
    Query,
    apply_delta,
    client_pack_key,
    graph_fingerprint,
    load_bundle,
    save_bundle,
)


@pytest.fixture(scope="module")
def tiny():
    return make_cora_like("tiny", seed=0)


def _random_delta(g, m, rng, extra_old_edges=0):
    """m new nodes, each wired to one old node (+ optional old-old edges)."""
    feats = g.features[rng.integers(0, g.num_nodes, size=m)].copy()
    n = g.num_nodes
    edges = [np.stack([np.arange(n, n + m), rng.integers(0, n, size=m)], axis=1)]
    for _ in range(extra_old_edges):
        i, j = rng.integers(0, n, size=2)
        edges.append(np.array([[i, j]]))
    return GraphDelta(features=feats, edges=np.concatenate(edges, axis=0))


# ---------------------------------------------------------------------------
# PackCache
# ---------------------------------------------------------------------------

def test_pack_cache_hit_miss_accounting():
    cache = PackCache()
    assert cache.get(0, "fp-a") is None                 # absent -> miss
    cache.put(0, PackEntry(pack="payload", fingerprint="fp-a"))
    hit = cache.get(0, "fp-a")
    assert hit is not None and hit.pack == "payload"
    assert cache.get(0, "fp-b") is None                 # stale -> miss
    s = cache.stats()
    assert (s["hits"], s["misses"], s["entries"]) == (1, 2, 1)


def test_pack_cache_lru_eviction():
    cache = PackCache(capacity=2)
    for c in range(3):
        cache.put(c, PackEntry(pack=c, fingerprint=f"fp{c}"))
    assert 0 not in cache and 1 in cache and 2 in cache
    assert cache.evictions == 1
    cache.get(1, "fp1")                                 # 1 becomes MRU
    cache.put(3, PackEntry(pack=3, fingerprint="fp3"))
    assert 2 not in cache and 1 in cache


def test_pack_cache_patch_refresh_revalidate():
    cache = PackCache()
    cache.put(0, PackEntry(pack="v0", fingerprint="fp0"))
    cache.note_patch(0, "fp1", "v1")
    e = cache.peek(0)
    assert e.patched and e.pack == "v1" and e.fingerprint == "fp1"
    cache.note_refresh(0, "fp2", "v2")
    e = cache.peek(0)
    assert not e.patched and e.builds == 2
    cache.revalidate(0, "fp3")
    assert cache.peek(0).fingerprint == "fp3"
    assert (cache.patches, cache.refreshes) == (1, 1)


def test_graph_fingerprint_sensitivity(tiny):
    base = graph_fingerprint(tiny.features, tiny.nbr_mask, extra=("matrix",))
    assert base == graph_fingerprint(tiny.features, tiny.nbr_mask, extra=("matrix",))
    assert base != graph_fingerprint(tiny.features, tiny.nbr_mask, extra=("vector",))
    bumped = tiny.features.copy()
    bumped[0, 0] += 1.0
    assert base != graph_fingerprint(bumped, tiny.nbr_mask, extra=("matrix",))


# ---------------------------------------------------------------------------
# Incremental updates: patched stream vs from-scratch, drift monotonicity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ["matrix", "vector"])
def test_refresh_matches_from_scratch_bitwise(tiny, engine):
    """A delta stream followed by a forced refresh must produce the pack a
    from-scratch precommunicate on the final graph would — bit for bit."""
    cfg = FedGATConfig(engine=engine)
    model = FedGAT(cfg)
    params = model.init(jax.random.PRNGKey(0), tiny)
    server = GraphInferenceServer(
        params, cfg, tiny, num_clients=2, refresh_threshold=1e9,
    )
    rng = np.random.default_rng(3)
    g = tiny
    server.serve_batch([Query(0, 0), Query(1, 1)])      # build packs
    for _ in range(3):
        delta = _random_delta(g, 2, rng, extra_old_edges=2)
        g = apply_delta(g, delta)
        server.apply_update(delta)
    assert server.cache.peek(0).patched                 # stream really patched
    server.refresh(0)
    fresh = model.refresh_pack(client_pack_key(server.pack_key, 0), g)
    for a, b in zip(fresh, server.pack_for(0)):
        if hasattr(a, "shape"):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        else:
            assert a == b
    # the refreshed graph arrays must equal an independent from-scratch build
    assert np.array_equal(g.nbr_idx, server.graph.nbr_idx)
    assert server.drift(0)["eps"] == 0.0
    assert not server.cache.peek(0).patched


def test_drift_monotone_between_refreshes(tiny):
    """Tracked Thm 3.5 eps never decreases while serving from patched packs,
    and resets to zero on refresh."""
    cfg = FedGATConfig(engine="matrix")
    params = FedGAT(cfg).init(jax.random.PRNGKey(0), tiny)
    server = GraphInferenceServer(
        params, cfg, tiny, num_clients=1, refresh_threshold=1e9,
    )
    server.serve_batch([Query(0, 0)])
    rng = np.random.default_rng(7)
    g = tiny
    for _ in range(4):
        delta = _random_delta(g, 1, rng, extra_old_edges=3)
        g = apply_delta(g, delta)
        server.apply_update(delta)
    hist = server.drift(0)["history"]
    assert len(hist) == 4 and hist[-1] > 0.0
    assert all(b >= a for a, b in zip(hist, hist[1:]))
    server.refresh(0)
    assert server.drift(0)["eps"] == 0.0


def test_bound_crossing_triggers_auto_refresh(tiny):
    cfg = FedGATConfig(engine="matrix")
    params = FedGAT(cfg).init(jax.random.PRNGKey(0), tiny)
    server = GraphInferenceServer(
        params, cfg, tiny, num_clients=1, refresh_threshold=1e-6,
    )
    server.serve_batch([Query(0, 0)])
    rng = np.random.default_rng(11)
    report = server.apply_update(_random_delta(tiny, 2, rng, extra_old_edges=4))
    assert report["refreshed"] == [0]
    assert server.drift(0)["eps"] == 0.0 and server.drift(0)["refreshes"] == 1


def test_packless_engine_absorbs_deltas_exactly(tiny):
    """direct/exact re-read the graph arrays: zero drift, logits match a
    from-scratch model on the grown graph."""
    cfg = FedGATConfig(engine="direct")
    params = FedGAT(cfg).init(jax.random.PRNGKey(0), tiny)
    server = GraphInferenceServer(params, cfg, tiny, num_clients=1)
    server.serve_batch([Query(0, 0)])
    rng = np.random.default_rng(5)
    delta = _random_delta(tiny, 2, rng)
    g2 = apply_delta(tiny, delta)
    report = server.apply_update(delta)
    assert report["drift"][0] == 0.0
    want = np.asarray(FedGAT(cfg).apply(params, g2))
    node = g2.num_nodes - 1
    got = server.serve_batch([Query(0, node)])[0]
    np.testing.assert_allclose(got.logits, want[node], atol=1e-6)


def test_apply_delta_validation(tiny):
    with pytest.raises(ValueError, match="dim"):
        apply_delta(tiny, GraphDelta(features=np.zeros((1, 3), np.float32)))
    with pytest.raises(ValueError, match="endpoints"):
        apply_delta(tiny, GraphDelta(edges=np.array([[0, tiny.num_nodes]])))


# ---------------------------------------------------------------------------
# Checkpoint round-trip: Trainer -> bundle -> server == FedGAT.apply
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def trained_bundle(tiny, tmp_path_factory):
    cfg = FederatedConfig(
        method="fedgat", num_clients=2, rounds=2, local_steps=1, seed=0,
        model=FedGATConfig(),
    )
    res = Trainer(cfg).run(tiny)
    path = tmp_path_factory.mktemp("bundle") / "ckpt"
    save_bundle(str(path), res["params"], cfg, step=2)
    return str(path), res["params"]


@pytest.mark.parametrize("engine", ["direct", "kernel"])
def test_served_logits_match_model_apply(tiny, trained_bundle, engine):
    path, params = trained_bundle
    server = GraphInferenceServer.from_checkpoint(path, tiny, engine=engine)
    assert server.cfg.engine == engine
    # loaded params are the trained ones
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(server.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    ref_model = FedGAT(dataclasses.replace(server.cfg))
    want = np.asarray(ref_model.apply(server.params, tiny))
    nodes = [0, 5, 17, tiny.num_nodes - 1]
    results = server.serve_batch([Query(c, n) for n in nodes for c in (0, 1)])
    for r in results:
        np.testing.assert_allclose(r.logits, want[r.node], atol=1e-6)
        assert r.label == int(np.argmax(want[r.node]))


def test_bundle_provenance_round_trip(tiny, trained_bundle):
    path, _params = trained_bundle
    ck = load_bundle(path, tiny)
    assert ck.meta["method"] == "fedgat" and ck.meta["num_clients"] == 2
    assert ck.meta["step"] == 2 and "beta" in ck.meta
    assert ck.model == FedGATConfig()
    assert ck.privacy == FederatedConfig().privacy


def test_distgat_checkpoint_rebuilds_partition(tiny, tmp_path):
    cfg = FederatedConfig(
        method="distgat", num_clients=2, rounds=1, local_steps=1, seed=0,
        model=FedGATConfig(),
    )
    res = Trainer(cfg).run(tiny)
    path = tmp_path / "distgat"
    save_bundle(str(path), res["params"], cfg, step=1)
    server = GraphInferenceServer.from_checkpoint(str(path), tiny)
    assert server.method == "distgat" and server.cfg.engine == "exact"
    part = dirichlet_partition(tiny.labels, 2, cfg.beta, cfg.seed)
    assert np.array_equal(server.part.owner, part.owner)
    # served logits respect the client's edge visibility
    mask = client_neighbor_masks(tiny, part, clients=[1])[0]
    want = np.asarray(
        FedGAT(server.cfg).apply(server.params, tiny, jnp.asarray(mask))
    )
    got = server.serve_batch([Query(1, 7)])[0]
    np.testing.assert_allclose(got.logits, want[7], atol=1e-6)


def test_distgat_requires_owners_for_new_nodes(tiny):
    cfg = FedGATConfig(engine="exact")
    params = FedGAT(cfg).init(jax.random.PRNGKey(0), tiny)
    part = dirichlet_partition(tiny.labels, 2, 1.0, 0)
    server = GraphInferenceServer(
        params, cfg, tiny, method="distgat", num_clients=2, partition=part,
    )
    rng = np.random.default_rng(0)
    with pytest.raises(ValueError, match="owners"):
        server.apply_update(_random_delta(tiny, 1, rng))
    delta = _random_delta(tiny, 1, rng)
    server.apply_update(delta._replace(owners=np.array([1])))
    assert server.part.owner.shape[0] == tiny.num_nodes + 1


# ---------------------------------------------------------------------------
# Engine resolution: serve as requested or raise
# ---------------------------------------------------------------------------

def test_kernel_request_raises_when_pallas_missing(tiny, monkeypatch):
    """A kernel request never degrades to another engine: with the Pallas
    stack unimportable, building the server raises."""
    import repro.kernels as kernels_pkg

    monkeypatch.setitem(sys.modules, "repro.kernels.ops", None)
    monkeypatch.delattr(kernels_pkg, "ops")
    params = FedGAT(FedGATConfig(engine="direct")).init(jax.random.PRNGKey(0), tiny)
    with pytest.raises(ImportError):
        GraphInferenceServer(params, FedGATConfig(engine="kernel"), tiny)


def test_unknown_engine_raises(tiny):
    params = FedGAT(FedGATConfig(engine="direct")).init(jax.random.PRNGKey(0), tiny)
    with pytest.raises(KeyError):
        GraphInferenceServer(params, FedGATConfig(), tiny, engine="nonsense")


# ---------------------------------------------------------------------------
# MicroBatcher
# ---------------------------------------------------------------------------

class FakeClock:
    """Each timer() call advances a fixed step -> every dispatch measures
    exactly one step of compute."""

    def __init__(self, step=0.0005):
        self.t = 0.0
        self.step = step

    def __call__(self):
        self.t += self.step
        return self.t


def test_microbatcher_size_and_deadline_dispatch():
    served = []

    def serve(batch):
        served.append(list(batch))
        return [q * 10 for q in batch]

    mb = MicroBatcher(serve, max_batch_size=3, max_wait=0.01, timer=FakeClock())
    out = mb.run([1, 2, 3, 4, 5], arrivals=[0.0, 0.001, 0.002, 0.05, 0.2])
    assert out == [10, 20, 30, 40, 50]                  # input order preserved
    assert served == [[1, 2, 3], [4], [5]]              # size, deadline, flush
    s = mb.stats.summary()
    assert s["batches"] == 3.0 and s["queries"] == 5.0
    np.testing.assert_allclose(s["mean_batch"], 5 / 3)  # exact count/mean


def test_microbatcher_queueing_under_load():
    step = 0.0005
    mb = MicroBatcher(
        lambda b: list(b), max_batch_size=2, max_wait=0.01, timer=FakeClock(step)
    )
    mb.run([0, 1, 2, 3])                                # all arrive at t=0
    # batch 2 queues behind batch 1: its completion is two compute steps out,
    # so the exact latencies are [step, step, 2*step, 2*step]. The bounded
    # histogram keeps count/mean exact and quantiles within 1%.
    lat = mb.stats.latency
    assert lat.count == 4
    np.testing.assert_allclose(lat.mean, 1.5 * step, rtol=1e-9)
    np.testing.assert_allclose(lat.vmin, step, atol=1e-12)
    np.testing.assert_allclose(lat.vmax, 2 * step, atol=1e-12)
    np.testing.assert_allclose(
        mb.stats.percentile_ms(99) / 1e3, 2 * step, rtol=0.01
    )
    s = mb.stats.summary()
    assert s["queries"] == 4 and s["batches"] == 2 and s["throughput_qps"] > 0


def test_microbatcher_validation():
    mb = MicroBatcher(lambda b: list(b), max_batch_size=2)
    with pytest.raises(ValueError, match="non-decreasing"):
        mb.run([1, 2], arrivals=[1.0, 0.5])
    with pytest.raises(ValueError, match="equal length"):
        mb.run([1, 2], arrivals=[0.0])
    bad = MicroBatcher(lambda b: [0], max_batch_size=8)
    with pytest.raises(RuntimeError, match="results"):
        bad.run([1, 2])
    with pytest.raises(ValueError):
        MicroBatcher(lambda b: b, max_batch_size=0)


# ---------------------------------------------------------------------------
# Benchmark contract + regression rules
# ---------------------------------------------------------------------------

def test_serve_bench_fast_smoke(tmp_path, monkeypatch):
    import benchmarks.common as common
    import benchmarks.serve_bench as sb

    monkeypatch.setattr(common, "REPO_ROOT", tmp_path)
    rows = sb.run(fast=True)
    assert rows and {"p50_ms", "p99_ms", "throughput_qps", "engine"} <= set(rows[0])
    assert all(r["p50_ms"] > 0 and r["throughput_qps"] > 0 for r in rows)
    assert "qps" in sb.derived(rows)
    emitted = json.loads((tmp_path / "BENCH_serve.json").read_text())
    assert emitted == rows


def test_check_regression_positive_keys(tmp_path):
    from benchmarks.check_regression import check_file

    good = tmp_path / "good.json"
    good.write_text(json.dumps([{"p50_ms": 1.0, "throughput_qps": 10.0}]))
    assert check_file(good) == []
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps([{"p50_ms": 0.0, "throughput_qps": 10.0}]))
    problems = check_file(bad)
    assert len(problems) == 1 and "p50_ms" in problems[0]


# ---------------------------------------------------------------------------
# PackCache persistence (survives server restarts)
# ---------------------------------------------------------------------------

def _make_server(g, cache_dir=None, num_clients=3, engine="matrix"):
    cfg = FedGATConfig(engine=engine)
    net = FedGAT(cfg)
    net.precommunicate(jax.random.PRNGKey(0), g)
    params = net.init(jax.random.PRNGKey(1), g)
    return GraphInferenceServer(
        params, cfg, g, num_clients=num_clients, cache_dir=cache_dir
    )


def test_pack_cache_save_load_round_trip(tiny, tmp_path):
    cache = PackCache(capacity=8)
    s1 = _make_server(tiny)
    s1.cache = cache
    r1 = s1.serve_batch([Query(0, 3), Query(1, 4), Query(2, 5)])
    saved = cache.save(str(tmp_path))
    assert saved["version"] == 1 and len(saved["entries"]) == 3

    loaded = PackCache.load(str(tmp_path))
    # counters and entry order survive
    assert loaded.stats() == cache.stats()
    assert list(loaded._entries) == list(cache._entries)
    for c in range(3):
        a, b = cache.peek(c), loaded.peek(c)
        assert a.fingerprint == b.fingerprint
        assert a.patched == b.patched and a.builds == b.builds
        for fa, fb in zip(a.pack, b.pack):
            np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))


def test_server_restart_warm_starts_from_cache_dir(tiny, tmp_path):
    cdir = str(tmp_path / "cache")
    s1 = _make_server(tiny, cache_dir=cdir)
    r1 = s1.serve_batch([Query(0, 3), Query(1, 9)])
    assert s1.cache.stats()["misses"] == 2
    s1.save_cache()

    # restart: packs reload, queries hit instead of rebuilding
    s2 = _make_server(tiny, cache_dir=cdir)
    assert len(s2.cache) == 2
    r2 = s2.serve_batch([Query(0, 3), Query(1, 9)])
    stats = s2.cache.stats()
    assert stats["misses"] == 2          # persisted counter; no NEW misses
    assert stats["hits"] >= 2
    for a, b in zip(r1, r2):
        np.testing.assert_array_equal(a.logits, b.logits)
        assert a.label == b.label


def test_loaded_cache_misses_on_changed_graph(tiny, tmp_path):
    cdir = str(tmp_path / "cache")
    s1 = _make_server(tiny, cache_dir=cdir)
    s1.serve_batch([Query(0, 3)])
    s1.save_cache()

    # the graph the restarted server sees differs -> fingerprint mismatch
    g2 = make_cora_like("tiny", seed=1)
    s2 = _make_server(g2, cache_dir=cdir)
    assert len(s2.cache) == 1
    before = s2.cache.stats()["misses"]
    s2.serve_batch([Query(0, 3)])
    assert s2.cache.stats()["misses"] == before + 1


def test_corrupted_payload_refuses_to_load(tiny, tmp_path):
    import glob

    cdir = str(tmp_path / "cache")
    s1 = _make_server(tiny, cache_dir=cdir)
    s1.serve_batch([Query(0, 3)])
    s1.save_cache()
    npz = glob.glob(str(tmp_path / "cache" / "*.npz"))[0]
    data = {k: v for k, v in np.load(npz).items()}
    first = next(iter(data))
    data[first] = data[first] + 1.0
    np.savez(npz, **data)
    with pytest.raises(ValueError, match="digest"):
        PackCache.load(cdir)


def test_save_cache_requires_a_directory(tiny):
    s = _make_server(tiny)
    with pytest.raises(ValueError, match="cache directory"):
        s.save_cache()
