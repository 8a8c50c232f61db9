"""Multi-process launcher (repro.launch.multiprocess): failure modes and
end-to-end metric parity.

The failure-mode tests drive :func:`launch` with tiny jax-free worker
commands, so they are fast and can't wedge the suite:

  * a worker that dies must take the whole gang down — the launcher
    propagates the non-zero exit AND reaps the surviving siblings (a dead
    SPMD participant deadlocks everyone else at the next collective);
  * an explicitly requested coordinator port that is already bound is an
    immediate, clear error — not a multi-minute distributed-init hang;
  * a hung gang is bounded by the launcher's wall-clock timeout.

The e2e test spawns the real CLI (2 processes x 2 forced host devices,
4 clients) and asserts the metrics it reports match the vmap backend run
in-process — the same cross-backend tolerance the single-host parity
tests use, now across process boundaries.
"""
import json
import os
import signal
import socket
import subprocess
import sys
import time

import pytest

from repro.launch import multiprocess as mp

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _env_with_src():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    return env


# ---------------------------------------------------------------------------
# Protocol / bootstrap units (no subprocesses)
# ---------------------------------------------------------------------------

def test_initialize_worker_is_noop_without_protocol():
    assert not mp.worker_env_active({})
    assert mp.initialize_worker({}) == (0, 1)


def test_force_host_device_count_merges_xla_flags(monkeypatch):
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_foo=1 --xla_force_host_platform_device_count=4"
    )
    mp.force_host_device_count(1)  # pre-existing larger count wins
    flags = os.environ["XLA_FLAGS"]
    assert "--xla_force_host_platform_device_count=4" in flags
    assert "--xla_foo=1" in flags


def test_cli_rejects_too_few_devices():
    with pytest.raises(SystemExit) as ei:
        mp.main(["--processes", "2", "--devices-per-process", "2",
                 "--clients", "8"])
    assert "8 clients" in str(ei.value)


def test_launch_rejects_bad_counts():
    with pytest.raises(ValueError):
        mp.launch(["true"], processes=0, devices_per_process=1)
    with pytest.raises(ValueError):
        mp.launch(["true"], processes=1, devices_per_process=0)


# ---------------------------------------------------------------------------
# Failure modes (jax-free worker commands)
# ---------------------------------------------------------------------------

def test_worker_failure_propagates_and_reaps_siblings(tmp_path):
    """Worker 1 exits 7 immediately; worker 0 would sleep for minutes. The
    launcher must return 7 fast and leave no surviving worker behind."""
    pid_file = tmp_path / "survivor.pid"
    script = (
        "import os, sys, time\n"
        f"if os.environ['{mp.ENV_PROCESS_ID}'] == '1':\n"
        "    sys.exit(7)\n"
        f"open({str(pid_file)!r}, 'w').write(str(os.getpid()))\n"
        "time.sleep(300)\n"
    )
    t0 = time.monotonic()
    code = mp.launch(
        [sys.executable, "-c", script], processes=2, devices_per_process=1,
        timeout=120,
    )
    elapsed = time.monotonic() - t0
    assert code == 7
    assert elapsed < 60, f"reaping took {elapsed:.1f}s"
    # The sibling recorded its pid before sleeping; it must be gone now.
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and not pid_file.exists():
        time.sleep(0.05)
    if pid_file.exists():  # it may have been killed before writing — fine
        survivor = int(pid_file.read_text())
        try:
            os.kill(survivor, 0)
            alive = True
        except OSError:
            alive = False
        assert not alive, f"worker {survivor} survived the reap"


def test_bound_coordinator_port_is_a_clear_error():
    """No hang, no spawn: the launcher refuses a busy port up front."""
    with socket.socket() as blocker:
        blocker.bind(("127.0.0.1", 0))
        blocker.listen(1)
        port = blocker.getsockname()[1]
        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match="already in use"):
            mp.launch(
                [sys.executable, "-c", "print('never runs')"],
                processes=2, devices_per_process=1, coordinator_port=port,
            )
        assert time.monotonic() - t0 < 5


def test_workers_are_pinned_to_cpu():
    """Workers simulate parties on host devices: an inherited accelerator
    platform must not reach them, or each would try to claim the chips."""
    script = "import os, sys; sys.exit(0 if os.environ['JAX_PLATFORMS'] == 'cpu' else 3)"
    code = mp.launch(
        [sys.executable, "-c", script], processes=2, devices_per_process=1,
        timeout=60, env={**os.environ, "JAX_PLATFORMS": "tpu"},
    )
    assert code == 0


def test_launch_timeout_bounds_a_hung_gang():
    t0 = time.monotonic()
    code = mp.launch(
        [sys.executable, "-c", "import time; time.sleep(300)"],
        processes=2, devices_per_process=1, timeout=3,
    )
    assert code == 124
    assert time.monotonic() - t0 < 30


# ---------------------------------------------------------------------------
# End-to-end: 2-process training matches the vmap backend
# ---------------------------------------------------------------------------

def test_two_process_training_matches_vmap(tmp_path):
    out = tmp_path / "mp.json"
    cmd = [
        sys.executable, "-m", "repro.launch.multiprocess",
        "--processes", "2", "--devices-per-process", "2",
        "--clients", "4", "--rounds", "2", "--local-steps", "1",
        "--engine", "direct", "--degree", "8", "--dataset", "tiny",
        "--out", str(out),
    ]
    res = subprocess.run(
        cmd, env=_env_with_src(), capture_output=True, text=True, timeout=600
    )
    assert res.returncode == 0, res.stderr[-3000:]
    summary = json.loads(out.read_text())
    assert summary["num_processes"] == 2
    assert summary["mesh"] == {
        "axis_names": ["clients"], "axis_sizes": [4],
        "num_devices": 4, "num_processes": 2, "platform": "cpu",
    }

    # Same schedule on the vmap backend in this (1-device) process: the
    # cross-backend tolerance the single-host parity tests use.
    import numpy as np

    from repro.core import FedGATConfig
    from repro.federated import FederatedConfig, run_federated
    from repro.graphs import make_cora_like

    g = make_cora_like("tiny", 0)
    cfg = FederatedConfig(
        method="fedgat", num_clients=4, rounds=2, local_steps=1,
        model=FedGATConfig(engine="direct", degree=8),
    )
    ref = run_federated(g, cfg, backend="vmap")
    np.testing.assert_allclose(ref["val_curve"], summary["val_curve"], atol=1e-6)
    np.testing.assert_allclose(ref["test_curve"], summary["test_curve"], atol=1e-6)
    assert abs(ref["best_test"] - summary["best_test"]) < 1e-6
