"""repro.telemetry: spans, metrics, manifests, and the zero-overhead-
when-disabled contract against the training/serving hot paths."""
import json
import math
import os
import re

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import telemetry
from repro.core import FedGATConfig
from repro.federated import FederatedConfig, run_federated
from repro.graphs import make_cora_like
from repro.privacy import PrivacyConfig
from repro.telemetry.metrics import Counter, Gauge, Histogram, MetricsRegistry


@pytest.fixture(autouse=True)
def _telemetry_off():
    """Every test starts and ends with telemetry disabled and clean span/
    event buffers (the registry is process-wide by design, so metrics are
    NOT reset — tests assert deltas, not absolutes)."""
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


@pytest.fixture(scope="module")
def graph():
    return make_cora_like("tiny", seed=0)


# ---------------------------------------------------------------------------
# Histogram: bounded memory, exact count/mean, <=1% quantile error
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.integers(0, 9999), st.integers(10, 400))
def test_histogram_quantile_matches_percentile(seed, n):
    rng = np.random.default_rng(seed)
    scale = 10.0 ** rng.integers(-6, 6)
    xs = rng.lognormal(mean=0.0, sigma=2.0, size=n) * scale
    h = Histogram("q")
    for x in xs:
        h.observe(float(x))
    for q in (0, 10, 50, 90, 99, 100):
        want = float(np.percentile(xs, q))
        got = h.quantile(q)
        assert got == pytest.approx(want, rel=0.01), (q, got, want)


def test_histogram_exact_moments_and_bounds():
    h = Histogram("m")
    xs = [0.5, 1.5, 2.0, 8.0]
    for x in xs:
        h.observe(x)
    assert h.count == 4
    assert h.mean == pytest.approx(np.mean(xs))
    assert h.total == pytest.approx(np.sum(xs))
    assert h.vmin == 0.5 and h.vmax == 8.0
    # quantile extremes are exact (under/overflow map to vmin/vmax)
    assert h.quantile(0) == 0.5
    assert h.quantile(100) == 8.0


def test_histogram_memory_is_bounded():
    h = Histogram("b")
    size0 = len(h._counts)
    for i in range(50_000):
        h.observe(1.0 + (i % 97) * 0.01)
    # the bucket array is fixed-size: observation count never grows it
    assert len(h._counts) == size0
    assert h.count == 50_000


def test_registry_kind_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")
    assert isinstance(reg.counter("x"), Counter)
    assert isinstance(reg.gauge("y"), Gauge)


# ---------------------------------------------------------------------------
# Spans: nesting, Chrome export schema, disabled no-op
# ---------------------------------------------------------------------------

def test_span_nesting_and_chrome_schema():
    telemetry.enable()
    with telemetry.span("outer", run=1):
        with telemetry.span("inner", step=2):
            pass
        with telemetry.span("inner", step=3):
            pass
    trace = telemetry.export_chrome_trace()
    assert trace["displayTimeUnit"] == "ms"
    ev = trace["traceEvents"]
    assert [e["name"] for e in ev] == ["inner", "inner", "outer"]
    for e in ev:
        assert e["ph"] == "X" and e["cat"] == "repro"
        assert e["dur"] >= 0 and isinstance(e["ts"], (int, float))
        assert "cpu_ms" in e["args"]
    inner = [e for e in ev if e["name"] == "inner"]
    assert all(e["args"]["parent"] == "outer" for e in inner)
    assert all(e["args"]["depth"] == 1 for e in inner)
    assert inner[0]["args"]["step"] == 2 and inner[1]["args"]["step"] == 3
    outer = ev[-1]
    assert outer["args"]["depth"] == 0 and outer["args"].get("parent") is None
    # trace must be JSON-serializable as-is
    json.loads(json.dumps(trace))


def test_disabled_span_is_shared_noop():
    assert not telemetry.enabled()
    s1 = telemetry.span("a", x=1)
    s2 = telemetry.span("b")
    assert s1 is s2 is telemetry.NULL_SPAN
    with s1:
        with s2:
            pass
    assert telemetry.export_chrome_trace()["traceEvents"] == []
    telemetry.event("nothing", x=1)  # events are dropped too


def test_events_jsonl_and_write_run(tmp_path, graph):
    telemetry.enable()
    telemetry.event("hello", round=1, eps=0.5)
    with telemetry.span("s"):
        pass
    paths = telemetry.write_run(str(tmp_path / "run"))
    for key in ("trace", "metrics", "manifest", "events"):
        assert os.path.exists(paths[key]), key
    trace = json.loads(open(paths["trace"]).read())
    assert {e["name"] for e in trace["traceEvents"]} == {"s"}
    man = json.loads(open(paths["manifest"]).read())
    assert man["versions"]["python"]
    lines = [json.loads(l) for l in open(paths["events"]) if l.strip()]
    assert lines[0]["event"] == "hello" and lines[0]["round"] == 1


# ---------------------------------------------------------------------------
# Disabled-mode bitwise parity: instrumentation must not move a single bit
# ---------------------------------------------------------------------------

def _parity_cfg(backend):
    return FederatedConfig(
        method="fedgat", backend=backend, num_clients=4, rounds=3,
        local_steps=2, lr=0.03,
        privacy=PrivacyConfig(noise_multiplier=0.8, clip=1.0, secure_agg=True),
        model=FedGATConfig(engine="kernel", degree=10),
    )


def _assert_bitwise_equal(r0, r1):
    assert r0["val_curve"] == r1["val_curve"]
    assert r0["test_curve"] == r1["test_curve"]
    import jax

    for a, b in zip(jax.tree.leaves(r0["params"]), jax.tree.leaves(r1["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_enabled_vs_disabled_bitwise_parity_vmap(graph):
    cfg = _parity_cfg("vmap")
    r0 = run_federated(graph, cfg)
    telemetry.enable()
    r1 = run_federated(graph, cfg)
    _assert_bitwise_equal(r0, r1)
    names = {e["name"] for e in telemetry.export_chrome_trace()["traceEvents"]}
    assert {"round", "step", "evaluate"} <= names


def test_enabled_vs_disabled_bitwise_parity_shard_map(graph):
    # devices < K on the default CPU backend, so this exercises the
    # cohort-streaming shard_map path (spans: round -> cohort -> step).
    cfg = _parity_cfg("shard_map")
    r0 = run_federated(graph, cfg)
    telemetry.enable()
    r1 = run_federated(graph, cfg)
    _assert_bitwise_equal(r0, r1)
    names = {e["name"] for e in telemetry.export_chrome_trace()["traceEvents"]}
    assert {"round", "cohort", "step", "staging"} <= names


def test_dp_run_records_epsilon_trajectory(graph):
    telemetry.enable()
    cfg = _parity_cfg("vmap")
    run_federated(graph, cfg)
    eps = telemetry.gauge("privacy.epsilon").value
    assert eps is not None and 0 < eps < math.inf


# ---------------------------------------------------------------------------
# Named device scopes: every part of the step's work carries its scope in
# the compiled ops' op names, forward and (where differentiated) backward
# ---------------------------------------------------------------------------

# Scopes inside the client loss's gradient, so their backward exists.
_DIFFERENTIATED = {"layer1", "layer2", "nbr_gather", "loss"}


def _op_names(hlo_text):
    return set(re.findall(r'op_name="([^"]*)"', hlo_text))


def _scopes_of(op_name):
    """(scope names along the op name's path, whether it is a backward op):
    ``a/transpose(jvp(layer2))/nbr_gather/x`` -> ({"a", "layer2",
    "nbr_gather", "x"}, True)."""
    parts = op_name.split("/")
    inner = {re.sub(r"^(?:[\w-]+\()+([^()]*)\)+$", r"\1", p) for p in parts}
    return inner, any(p.startswith("transpose(") for p in parts)


@pytest.fixture(scope="module")
def scoped_programs(graph):
    """Op names of the compiled cohort step and evaluate program of a small
    FedGAT job on the ``kernel`` engine (Pallas interpreted on the CPU),
    built as ``run_cohort_rounds`` builds them."""
    import jax
    import jax.numpy as jnp

    from repro.federated import cohort, trainer
    from repro.federated.aggregation import RunningAggregate
    from repro.federated.partition import dirichlet_partition, stage_cohort_masks
    from repro.optim.adamw import adam_init

    cfg = FederatedConfig(
        method="fedgat", num_clients=4, rounds=1, local_steps=1,
        max_concurrent_clients=2, model=FedGATConfig(engine="kernel", degree=4),
    )
    K, lanes = cfg.num_clients, 2
    k_pack, k_init = jax.random.split(jax.random.PRNGKey(cfg.seed))
    init_fn, forward, data = trainer.build_forward(cfg, graph, k_pack)
    params = init_fn(k_init)
    part = dirichlet_partition(graph.labels, K, cfg.beta, cfg.seed)
    _, tr = stage_cohort_masks(graph, part, (0, 1), lanes, neighbor=False)
    step = cohort.make_vmap_cohort_step(
        cfg, trainer.make_local_update(trainer.make_loss_fn(forward), cfg), K
    )
    agg = RunningAggregate(
        sum=jax.tree.map(jnp.zeros_like, params), weight=jnp.zeros((), jnp.float32)
    )
    opt = jax.vmap(lambda _: adam_init(params))(jnp.arange(lanes))
    step_text = step.lower(
        params, agg, opt, data, data["nbr_mask"], tr,
        jnp.arange(lanes, dtype=jnp.int32), jnp.ones(lanes, jnp.float32),
        jnp.float32(1.0), jnp.zeros(K, jnp.float32), jnp.int32(0),
    ).compile().as_text()
    eval_text = trainer.make_evaluate(forward).lower(params, data).compile().as_text()
    return {"step": step_text, "evaluate": eval_text}


@pytest.mark.parametrize("scope", telemetry.DEVICE_SCOPES)
def test_device_scope_names_the_steps_ops(scoped_programs, scope):
    names = [n for n in _op_names(scoped_programs["step"]) if n.startswith("jit(step)/")]
    found = [_scopes_of(n) for n in names if scope in _scopes_of(n)[0]]
    assert any(not bwd for _, bwd in found), f"no forward op under {scope!r}"
    if scope in _DIFFERENTIATED:
        assert any(bwd for _, bwd in found), f"no backward op under {scope!r}"


def test_evaluate_program_is_named_and_scoped(scoped_programs):
    text = scoped_programs["evaluate"]
    assert text.startswith("HloModule jit_evaluate")
    assert "<unknown>" not in text
    # (The interpreted Pallas body's loop carries relative names.)
    paths = [n for n in _op_names(text) if n.startswith("jit(")]
    assert paths and all(n.startswith("jit(evaluate)/") for n in paths)
    scopes = set().union(*(_scopes_of(n)[0] for n in paths))
    assert {"layer1", "layer2", "nbr_gather"} <= scopes


def test_jitted_programs_compile_under_their_names(graph):
    """A FedAdam cohort job compiles ``step``, ``evaluate`` and
    ``server_apply``, and no program of the trainer is ``<unknown>``."""
    from jax import monitoring

    compiled = []

    def on_duration(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(kw.get("fun_name"))

    cfg = FederatedConfig(
        method="fedgat", num_clients=4, rounds=1, local_steps=1,
        max_concurrent_clients=2, aggregator="fedadam",
        model=FedGATConfig(engine="kernel", degree=4),
    )
    monitoring.register_event_duration_secs_listener(on_duration)
    try:
        run_federated(graph, cfg)
    finally:
        monitoring.unregister_event_duration_listener(on_duration)
    assert {"jit(step)", "jit(evaluate)", "jit(server_apply)"} <= set(compiled)
    assert not {"jit(<unknown>)", "jit(<lambda>)"} & set(compiled), compiled


# ---------------------------------------------------------------------------
# Unified counters: legacy accessors stay views over the registry
# ---------------------------------------------------------------------------

def test_dense_view_count_is_registry_backed(graph):
    from repro.graphs import graph as graph_mod

    graph_mod.reset_dense_view_count()
    before = telemetry.counter("graphs.dense_view_count").value
    assert before == 0 and graph_mod.dense_view_count() == 0
    graph_mod.dense_adjacency(graph)
    assert graph_mod.dense_view_count() == 1
    assert telemetry.counter("graphs.dense_view_count").value == 1


def test_pack_cache_feeds_registry_counters():
    from repro.serving.cache import PackCache, PackEntry

    before = {
        k: telemetry.counter(f"serving.pack_cache.{k}").value
        for k in ("hits", "misses", "evictions")
    }
    c = PackCache(capacity=1)
    assert c.get(0, "fp") is None                       # miss
    c.put(0, PackEntry(pack=None, fingerprint="fp"))
    assert c.get(0, "fp") is not None                   # hit
    c.put(1, PackEntry(pack=None, fingerprint="fp2"))   # evicts client 0
    assert c.stats()["hits"] == 1 and c.stats()["misses"] == 1
    assert c.stats()["evictions"] == 1
    for k, want in (("hits", 1), ("misses", 1), ("evictions", 1)):
        got = telemetry.counter(f"serving.pack_cache.{k}").value - before[k]
        assert got == want, (k, got)


def test_latency_stats_bounded_with_stable_summary_keys():
    from repro.serving.scheduler import LatencyStats

    stats = LatencyStats()
    for i in range(10_000):
        stats.observe_batch([i * 1e-3], i * 1e-3 + 0.005 + (i % 7) * 1e-4)
    s = stats.summary()
    assert set(s) == {
        "queries", "batches", "mean_batch", "p50_ms", "p99_ms",
        "throughput_qps", "span_s",
    }
    assert s["queries"] == 10_000.0 and s["mean_batch"] == 1.0
    assert 0 < s["p50_ms"] <= s["p99_ms"]
    # bounded: the sketch is a fixed-size array, not a per-query list
    assert len(stats.latency._counts) == stats.latency._nb + 2


# ---------------------------------------------------------------------------
# Manifest: provenance through build_result and checkpoint bundles
# ---------------------------------------------------------------------------

def test_build_result_manifest_and_json_clean(graph):
    cfg = FederatedConfig(
        method="fedgat", num_clients=3, rounds=1, local_steps=1,
        model=FedGATConfig(engine="direct", degree=4),
    )
    res = run_federated(graph, cfg)
    man = res["manifest"]
    assert man["jit_compiles"] > 0
    assert man["backend"] == "vmap"
    assert man["jax_backend"] and man["versions"]["jax"]
    assert len(man["config_hash"]) == 40
    json.dumps(man)  # must serialize as-is


def test_manifest_round_trips_through_bundle(tmp_path, graph):
    from repro.serving.checkpoint import load_bundle, save_bundle

    cfg = FederatedConfig(
        method="fedgat", num_clients=2, rounds=1, local_steps=1,
        model=FedGATConfig(engine="direct", degree=4),
    )
    res = run_federated(graph, cfg)
    save_bundle(str(tmp_path), res["params"], cfg)
    bundle = load_bundle(str(tmp_path), graph)
    man = bundle.meta["manifest"]
    assert man["jit_compiles"] > 0
    assert man["config_hash"] == res["manifest"]["config_hash"]


def test_config_hash_is_content_addressed():
    from repro.telemetry.manifest import config_hash

    a = FederatedConfig(num_clients=4)
    b = FederatedConfig(num_clients=4)
    c = FederatedConfig(num_clients=5)
    assert config_hash(a) == config_hash(b)
    assert config_hash(a) != config_hash(c)


# ---------------------------------------------------------------------------
# check_regression trajectory mode (pure compare — no git involved)
# ---------------------------------------------------------------------------

def _load_check_regression():
    import importlib.util
    import pathlib

    p = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "check_regression.py"
    spec = importlib.util.spec_from_file_location("check_regression", p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trajectory_compare_flags_directional_regressions():
    cr = _load_check_regression()
    base = [{"name": "serve", "clients": 8, "p99_ms": 10.0, "throughput_qps": 100.0}]
    ok = [{"name": "serve", "clients": 8, "p99_ms": 12.0, "throughput_qps": 90.0}]
    probs, matched = cr.check_trajectory_rows(ok, base, tolerance=1.5)
    assert matched == 1 and probs == []
    slow = [{"name": "serve", "clients": 8, "p99_ms": 16.0, "throughput_qps": 100.0}]
    probs, _ = cr.check_trajectory_rows(slow, base, tolerance=1.5)
    assert len(probs) == 1 and "p99_ms" in probs[0]
    starved = [{"name": "serve", "clients": 8, "p99_ms": 10.0, "throughput_qps": 50.0}]
    probs, _ = cr.check_trajectory_rows(starved, base, tolerance=1.5)
    assert len(probs) == 1 and "throughput_qps" in probs[0]


def test_trajectory_unmatched_rows_are_not_failures():
    cr = _load_check_regression()
    base = [{"name": "serve", "clients": 8, "p99_ms": 10.0}]
    cur = [{"name": "serve", "clients": 16, "p99_ms": 500.0}]  # new sweep point
    probs, matched = cr.check_trajectory_rows(cur, base, tolerance=1.5)
    assert matched == 0 and probs == []


def test_trajectory_row_identity_ignores_measured_ints():
    cr = _load_check_regression()
    a = {"name": "serve", "clients": 8, "batches": 100, "p99_ms": 1.0}
    b = {"name": "serve", "clients": 8, "batches": 999, "p99_ms": 1.0}
    assert cr.row_identity(a) == cr.row_identity(b)
    c = dict(a, clients=16)
    assert cr.row_identity(a) != cr.row_identity(c)
