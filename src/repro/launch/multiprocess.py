"""Multi-process launcher for the shard_map federated backend.

``federated/sharded.py`` runs unchanged as a multi-controller SPMD program
once ``jax.distributed.initialize`` has been called in every participating
process: the client mesh spans processes × local devices, each process
feeds only its addressable client shards, and the psum aggregation / CS(t)
selection / privacy noise streams are keyed by the *global* client axis —
so a 2-process × 2-device run reproduces the 1-process × 4-device run
exactly. This module is the piece that stands those processes up.

Two halves, one env-var protocol:

* **Launcher** (:func:`launch`): spawns N copies of a worker command on
  this host, each with ``REPRO_MP_*`` env vars carrying the coordinator
  address, process id/count and forced host device count, and with
  ``JAX_PLATFORMS=cpu``: the workers simulate parties on host devices, and
  on a machine with accelerators they must not each try to claim them. It
  babysits the
  workers: the first non-zero exit reaps every sibling and becomes the
  launcher's own exit code; a wall-clock timeout bounds hangs; an
  explicitly requested coordinator port that is already bound is a clear
  immediate error, not a stuck barrier.

* **Worker bootstrap** (:func:`initialize_worker`): called in the child
  BEFORE any jax device use. Reads the protocol env vars, forces the local
  host device count (CPU simulation), selects the Gloo CPU collectives
  backend and calls ``jax.distributed.initialize`` with a bounded
  initialization timeout. A process without the env vars is a no-op
  single-process run — library code can call this unconditionally.

CLI (also the CI end-to-end proof)::

    python -m repro.launch.multiprocess \
        --processes 2 --devices-per-process 4 --clients 8 \
        --rounds 3 --aggregator fedadam --client-fraction 0.5 \
        --noise-multiplier 0.5 --clip 1.0 --secure-agg --out result.json

trains the federated clients through the shard_map backend over the global
mesh; process 0 prints a one-line JSON summary and writes ``--out``.

This is single-host **multi-process** (the deployment shape of cross-silo
federated learning, one OS process per party); multi-*machine* needs only
the coordinator address to point at a reachable host and each machine to
run its own block of process ids — the training code is already global.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import socket
import subprocess
import sys
import time
from typing import Dict, List, Optional, Sequence

ENV_COORDINATOR = "REPRO_MP_COORDINATOR"
ENV_NUM_PROCESSES = "REPRO_MP_NUM_PROCESSES"
ENV_PROCESS_ID = "REPRO_MP_PROCESS_ID"
ENV_DEVICES = "REPRO_MP_DEVICES_PER_PROCESS"
ENV_INIT_TIMEOUT = "REPRO_MP_INIT_TIMEOUT"

_PROTOCOL_VARS = (
    ENV_COORDINATOR, ENV_NUM_PROCESSES, ENV_PROCESS_ID, ENV_DEVICES,
    ENV_INIT_TIMEOUT,
)

_DEVICE_COUNT_RE = re.compile(r"--xla_force_host_platform_device_count=(\d+)")


def worker_env_active(env: Optional[Dict[str, str]] = None) -> bool:
    """True when this process was spawned by :func:`launch`."""
    return ENV_COORDINATOR in (os.environ if env is None else env)


def force_host_device_count(n: int) -> None:
    """Ensure ``XLA_FLAGS`` forces >= ``n`` host devices.

    Must run before jax initialises its backend (the count locks on first
    device use). A pre-existing larger count wins; a smaller one is raised.
    """
    existing = os.environ.get("XLA_FLAGS", "")
    m = _DEVICE_COUNT_RE.search(existing)
    count = max(n, int(m.group(1))) if m else n
    rest = _DEVICE_COUNT_RE.sub("", existing).strip()
    os.environ["XLA_FLAGS"] = (
        f"{rest} --xla_force_host_platform_device_count={count}".strip()
    )
    if "jax" in sys.modules:
        import jax

        if jax.local_device_count() < n:
            raise RuntimeError(
                f"need >= {n} local devices but jax already initialised "
                f"with {jax.local_device_count()}; set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={n} before the "
                "first jax device use"
            )


def initialize_worker(env: Optional[Dict[str, str]] = None) -> tuple:
    """Worker-side bootstrap; returns ``(process_id, num_processes)``.

    No-op ``(0, 1)`` when the launcher protocol is absent, so entry points
    can call it unconditionally. Otherwise forces the local device count,
    switches the CPU backend to Gloo collectives (the only CPU backend that
    implements cross-process computations) and joins the coordinator with a
    bounded initialization timeout.
    """
    e = os.environ if env is None else env
    if not worker_env_active(e):
        return 0, 1
    process_id = int(e[ENV_PROCESS_ID])
    num_processes = int(e[ENV_NUM_PROCESSES])
    force_host_device_count(int(e[ENV_DEVICES]))
    import jax

    if num_processes > 1:
        # Gloo needs the distributed client: set it only when one exists.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            coordinator_address=e[ENV_COORDINATOR],
            num_processes=num_processes,
            process_id=process_id,
            initialization_timeout=int(float(e.get(ENV_INIT_TIMEOUT, "60"))),
        )
    return process_id, num_processes


def free_coordinator_port() -> int:
    """An OS-assigned free TCP port for the coordinator."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _check_port_free(port: int) -> None:
    try:
        with socket.socket() as s:
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            s.bind(("127.0.0.1", port))
    except OSError as err:
        raise RuntimeError(
            f"coordinator port {port} is already in use ({err}); pick a "
            "free port or omit --coordinator-port to auto-assign one"
        ) from None


def _reap(procs: Sequence[subprocess.Popen], grace: float = 5.0) -> None:
    """Terminate every still-running worker (SIGTERM, then SIGKILL)."""
    for p in procs:
        if p.poll() is None:
            p.terminate()
    deadline = time.monotonic() + grace
    for p in procs:
        if p.poll() is None:
            try:
                p.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()


def launch(
    cmd: Sequence[str],
    *,
    processes: int,
    devices_per_process: int,
    coordinator_port: Optional[int] = None,
    timeout: float = 900.0,
    init_timeout: float = 60.0,
    env: Optional[Dict[str, str]] = None,
) -> int:
    """Run ``cmd`` as ``processes`` cooperating workers; return an exit code.

    Each worker inherits this environment plus the ``REPRO_MP_*`` protocol
    vars (:func:`initialize_worker` consumes them), pinned to the CPU
    backend with ``JAX_PLATFORMS=cpu``. Failure semantics:

    * any worker exiting non-zero reaps every sibling and its code is
      returned (the death of one SPMD participant deadlocks the rest at
      their next collective — they must not linger);
    * ``timeout`` seconds without completion reaps everything and returns
      124 (the ``timeout(1)`` convention);
    * an explicitly requested ``coordinator_port`` that is already bound
      raises ``RuntimeError`` before anything is spawned.
    """
    if processes < 1:
        raise ValueError(f"processes must be >= 1, got {processes}")
    if devices_per_process < 1:
        raise ValueError(
            f"devices_per_process must be >= 1, got {devices_per_process}"
        )
    if coordinator_port is None:
        coordinator_port = free_coordinator_port()
    else:
        _check_port_free(coordinator_port)

    base = dict(os.environ if env is None else env)
    for var in _PROTOCOL_VARS:   # never inherit a stale protocol
        base.pop(var, None)

    procs: List[subprocess.Popen] = []
    try:
        for i in range(processes):
            wenv = dict(base)
            wenv[ENV_COORDINATOR] = f"127.0.0.1:{coordinator_port}"
            wenv[ENV_NUM_PROCESSES] = str(processes)
            wenv[ENV_PROCESS_ID] = str(i)
            wenv[ENV_DEVICES] = str(devices_per_process)
            wenv[ENV_INIT_TIMEOUT] = str(init_timeout)
            wenv["JAX_PLATFORMS"] = "cpu"
            procs.append(subprocess.Popen(list(cmd), env=wenv))
        deadline = time.monotonic() + timeout
        while True:
            codes = [p.poll() for p in procs]
            bad = [c for c in codes if c not in (None, 0)]
            if bad:
                _reap(procs)
                print(
                    f"[multiprocess] worker died with exit code {bad[0]}; "
                    "reaped remaining workers",
                    file=sys.stderr, flush=True,
                )
                return int(bad[0])
            if all(c == 0 for c in codes):
                return 0
            if time.monotonic() > deadline:
                _reap(procs)
                print(
                    f"[multiprocess] timed out after {timeout:.0f}s; "
                    "reaped all workers",
                    file=sys.stderr, flush=True,
                )
                return 124
            time.sleep(0.1)
    finally:
        _reap(procs)


def launch_self(
    argv: Sequence[str],
    *,
    processes: int,
    devices_per_process: int,
    coordinator_port: Optional[int] = None,
    timeout: float = 900.0,
) -> int:
    """Re-run ``sys.executable argv`` as N workers (argv[0] is the script).

    Used by entry points that are their own worker: the re-exec carries the
    same argv, and the child detects worker mode via the protocol env vars.
    """
    return launch(
        [sys.executable, *argv],
        processes=processes,
        devices_per_process=devices_per_process,
        coordinator_port=coordinator_port,
        timeout=timeout,
    )


# ---------------------------------------------------------------------------
# CLI: federated training over the multi-process mesh
# ---------------------------------------------------------------------------

def _parse(argv: Optional[Sequence[str]]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.multiprocess",
        description="train the federated shard_map backend over a "
        "multi-process mesh (CPU simulation of cross-silo deployment)",
    )
    ap.add_argument("--processes", type=int, default=2)
    ap.add_argument("--devices-per-process", type=int, default=2)
    ap.add_argument("--coordinator-port", type=int, default=None,
                    help="coordinator TCP port (default: auto-assign)")
    ap.add_argument("--timeout", type=float, default=900.0,
                    help="launcher wall-clock bound in seconds")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--local-steps", type=int, default=1)
    ap.add_argument("--aggregator", default="fedavg",
                    choices=["fedavg", "fedprox", "fedadam"])
    ap.add_argument("--client-fraction", type=float, default=1.0)
    ap.add_argument("--method", default="fedgat",
                    choices=["fedgat", "distgat", "fedgcn"])
    ap.add_argument("--engine", default="direct",
                    help="layer-1 engine for fedgat (registry name)")
    ap.add_argument("--degree", type=int, default=8)
    ap.add_argument("--dataset", default="tiny")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--noise-multiplier", type=float, default=0.0)
    ap.add_argument("--clip", type=float, default=float("inf"))
    ap.add_argument("--secure-agg", action="store_true")
    ap.add_argument("--out", default=None,
                    help="process 0 writes the result summary JSON here")
    return ap.parse_args(argv)


def result_summary(res: Dict, num_processes: int) -> Dict:
    """The JSON-serialisable slice of a Trainer result (params dropped)."""
    return {
        "backend": res["backend"],
        "num_processes": num_processes,
        "mesh": res["mesh"],
        "val_curve": res["val_curve"],
        "test_curve": res["test_curve"],
        "best_val": res["best_val"],
        "best_test": res["best_test"],
        "final_test": res["final_test"],
        "epsilon": res["epsilon"],
        "seconds": res["seconds"],
    }


def _worker_main(args: argparse.Namespace) -> int:
    process_id, num_processes = initialize_worker()

    from repro.core.fedgat_model import FedGATConfig
    from repro.federated.trainer import FederatedConfig, run_federated
    from repro.graphs import make_cora_like
    from repro.privacy import PrivacyConfig

    g = make_cora_like(args.dataset, args.seed)
    cfg = FederatedConfig(
        method=args.method,
        backend="shard_map",
        num_clients=args.clients,
        rounds=args.rounds,
        local_steps=args.local_steps,
        aggregator=args.aggregator,
        client_fraction=args.client_fraction,
        seed=args.seed,
        model=FedGATConfig(engine=args.engine, degree=args.degree),
        privacy=PrivacyConfig(
            noise_multiplier=args.noise_multiplier,
            clip=args.clip,
            secure_agg=args.secure_agg,
            # The field-masking protocol needs the host-side cohort driver,
            # which is single-process; across processes the in-jit pairwise
            # masks (cancelling inside the cross-process psum) are the
            # supported mode.
            secure_agg_mode="pairwise",
        ),
    )
    res = run_federated(g, cfg)
    if process_id == 0:
        summary = result_summary(res, num_processes)
        print("RESULT " + json.dumps(summary), flush=True)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(summary, f, indent=1)
    return 0


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = _parse(argv)
    if worker_env_active():
        return _worker_main(args)
    if args.processes * args.devices_per_process < args.clients:
        raise SystemExit(
            f"{args.clients} clients need >= {args.clients} devices but "
            f"--processes {args.processes} x --devices-per-process "
            f"{args.devices_per_process} provides only "
            f"{args.processes * args.devices_per_process}"
        )
    return launch_self(
        ["-m", "repro.launch.multiprocess", *(argv or sys.argv[1:])],
        processes=args.processes,
        devices_per_process=args.devices_per_process,
        coordinator_port=args.coordinator_port,
        timeout=args.timeout,
    )


if __name__ == "__main__":
    raise SystemExit(main())
