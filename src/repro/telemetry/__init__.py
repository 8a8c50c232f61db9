"""repro.telemetry — tracing, metrics, and run manifests for the stack.

One observability layer for training (Trainer / cohort rounds), serving
(GraphInferenceServer / MicroBatcher), privacy (epsilon trajectory) and
the benchmark drivers:

* **Spans** — ``with telemetry.span("round", round=t): ...`` nest through
  a thread-local stack, time wall + process CPU, and export as
  Chrome-trace JSON (``chrome://tracing`` / Perfetto). Disabled (the
  default) ``span()`` returns a shared no-op context manager: no record,
  no allocation, one flag check — instrumentation lives at host-side
  boundaries only, so the jitted computations are untouched either way.
* **Metrics** — a process-wide registry (:mod:`repro.telemetry.metrics`)
  of counters/gauges/bounded histograms. The pre-existing ad hoc counters
  (``graphs.dense_view_count``, ``PackCache`` accounting, cohort churn)
  register here; metrics are always live (they always were).
* **Events** — a structured JSONL sink (``telemetry.event(...)``), fed
  only when enabled.
* **Manifests** — :func:`manifest` builds the per-run provenance block
  (config hash, backend, mesh, jit-compile count via ``jax.monitoring``,
  package versions) that ``build_result`` and serving bundles attach.

Activation: ``telemetry.enable()`` / ``telemetry.disable()``
programmatically, or the ``REPRO_TELEMETRY=1`` env var at import time
(with ``REPRO_TELEMETRY_DIR=path`` to auto-write the run artifacts —
trace.json, metrics.json, manifest.json, events.jsonl — at process exit).
"""
from __future__ import annotations

import atexit
import json
import os
import sys
from typing import Any, Dict, Optional

from repro.telemetry import metrics as metrics  # re-export module
from repro.telemetry.manifest import build_manifest, config_hash
from repro.telemetry.metrics import counter, gauge, histogram
from repro.telemetry.sink import EventSink
from repro.telemetry.tracing import NULL_SPAN, SpanRecord, Tracer

__all__ = [
    "enabled", "enable", "disable", "configure", "reset",
    "span", "event", "tracer",
    "counter", "gauge", "histogram", "metrics", "metrics_snapshot",
    "manifest", "build_manifest", "config_hash",
    "jit_compile_count", "jit_compile_seconds", "install_jax_hooks",
    "export_chrome_trace", "write_run",
    "SpanRecord", "Tracer", "EventSink", "NULL_SPAN", "DEVICE_SCOPES",
]

_enabled = False
_out_dir: Optional[str] = None
_atexit_registered = False

tracer = Tracer()
_events = EventSink()

# jit-compile accounting: one count/one duration sum per XLA backend
# compile, fed by the jax.monitoring listener below. Counters live in the
# registry so they appear in metrics snapshots and manifests alike.
_JIT_COMPILES = counter("jax.jit_compiles")
_JIT_COMPILE_S = gauge("jax.jit_compile_seconds")
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_hooks_installed = False


def install_jax_hooks() -> bool:
    """Register the ``jax.monitoring`` listener that counts XLA backend
    compiles. Idempotent; a no-op (returning False) when jax is absent.
    Called automatically on :func:`enable` and by the Trainer at import,
    so any training process counts compiles from its first round."""
    global _hooks_installed
    if _hooks_installed:
        return True
    try:
        from jax import monitoring
    except Exception:
        return False

    def _on_duration(event: str, duration: float, **kw) -> None:
        if event == _COMPILE_EVENT:
            _JIT_COMPILES.inc()
            prev = _JIT_COMPILE_S.value or 0.0
            _JIT_COMPILE_S.set(prev + float(duration))

    monitoring.register_event_duration_secs_listener(_on_duration)
    _hooks_installed = True
    return True


def jit_compile_count() -> int:
    return _JIT_COMPILES.value


def jit_compile_seconds() -> float:
    return float(_JIT_COMPILE_S.value or 0.0)


# ---------------------------------------------------------------------------
# The switch
# ---------------------------------------------------------------------------

def enabled() -> bool:
    return _enabled


def enable(out_dir: Optional[str] = None) -> None:
    """Turn tracing/events on (metrics are always on). With ``out_dir``,
    the run artifacts are written there at process exit (and by any
    explicit :func:`write_run` call)."""
    global _enabled, _out_dir, _atexit_registered
    _enabled = True
    install_jax_hooks()
    if out_dir is not None:
        _out_dir = out_dir
        if not _atexit_registered:
            atexit.register(_write_run_atexit)
            _atexit_registered = True


def disable() -> None:
    global _enabled
    _enabled = False


def configure(*, enabled: bool, out_dir: Optional[str] = None) -> None:
    if enabled:
        enable(out_dir)
    else:
        disable()


def reset(reset_metrics: bool = False) -> None:
    """Clear span/event buffers (and optionally zero all metrics) —
    primarily for tests and for long-lived processes rotating traces."""
    tracer.reset()
    _events.reset()
    if reset_metrics:
        metrics.registry().reset()


# ---------------------------------------------------------------------------
# Hot-path entry points
# ---------------------------------------------------------------------------

def span(name: str, /, **args):
    """A timed, nested span when telemetry is enabled; a shared no-op
    context manager when disabled (the common case — near-zero cost).
    ``name`` is positional-only so ``name=...`` stays usable as a span
    attribute."""
    if not _enabled:
        return NULL_SPAN
    return tracer.span(name, **args)


# The ``jax.named_scope`` names the programs give their device work. They
# change no computation: they land in each compiled op's ``op_name``
# metadata, and so in a profiler trace's device ops, forward ops as
# ``jvp(<scope>)`` or ``<scope>`` and their backward under ``transpose(``.
# "layer1" is FedGAT's approximate layer and "layer<l>" each exact GAT
# layer after it; "nbr_gather" the neighbour gathers inside them (their
# backward a gather with the graph's reverse-slot table, a scatter-add
# without; repro.core.nbr_gather); "loss" the client loss; "adam" the client
# optimizer step; "fold" the in-step FedAvg fold with its secure-agg masks.
DEVICE_SCOPES = ("layer1", "layer2", "nbr_gather", "loss", "adam", "fold")


def event(name: str, **fields) -> None:
    """Emit a structured event to the JSONL sink (enabled runs only)."""
    if _enabled:
        _events.emit(name, **fields)


def metrics_snapshot() -> Dict[str, Dict[str, Any]]:
    return metrics.snapshot()


def manifest(cfg: Any = None, *, mesh: Optional[Dict[str, Any]] = None,
             extra: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """The per-run provenance manifest (see telemetry.manifest)."""
    install_jax_hooks()
    return build_manifest(cfg, mesh=mesh, extra=extra)


# ---------------------------------------------------------------------------
# Export
# ---------------------------------------------------------------------------

def export_chrome_trace(path: Optional[str] = None) -> Dict[str, Any]:
    """The collected spans as a Chrome-trace JSON object; written to
    ``path`` when given."""
    trace = tracer.to_chrome()
    if path is not None:
        with open(path, "w") as f:
            json.dump(trace, f)
    return trace


def write_run(out_dir: str, cfg: Any = None) -> Dict[str, str]:
    """Write the full run artifact set under ``out_dir``:

    ``trace.json`` (Chrome trace), ``metrics.json`` (registry snapshot),
    ``manifest.json`` (provenance), ``events.jsonl`` (structured events).
    Returns {artifact: path}.
    """
    os.makedirs(out_dir, exist_ok=True)
    paths = {
        "trace": os.path.join(out_dir, "trace.json"),
        "metrics": os.path.join(out_dir, "metrics.json"),
        "manifest": os.path.join(out_dir, "manifest.json"),
        "events": os.path.join(out_dir, "events.jsonl"),
    }
    export_chrome_trace(paths["trace"])
    with open(paths["metrics"], "w") as f:
        json.dump(metrics_snapshot(), f, indent=1, default=str)
    with open(paths["manifest"], "w") as f:
        json.dump(manifest(cfg), f, indent=1, default=str)
    _events.write_jsonl(paths["events"])
    return paths


def _write_run_atexit() -> None:
    if _enabled and _out_dir:
        try:
            write_run(_out_dir)
        except Exception as err:  # never fail interpreter shutdown
            print(f"repro.telemetry: atexit write failed: {err}",
                  file=sys.stderr)


# ---------------------------------------------------------------------------
# Env activation (REPRO_TELEMETRY=1 [REPRO_TELEMETRY_DIR=path])
# ---------------------------------------------------------------------------

_env = os.environ.get("REPRO_TELEMETRY", "").strip().lower()
if _env in ("1", "true", "yes", "on"):
    enable(os.environ.get("REPRO_TELEMETRY_DIR") or None)
del _env
