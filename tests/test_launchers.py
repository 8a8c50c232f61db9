"""Launcher CLIs (train/serve) and dry-run artifact integrity."""
import json
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).parent.parent


def _run(args, timeout=560):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    return subprocess.run(
        [sys.executable, "-m"] + args, env=env, capture_output=True, text=True,
        timeout=timeout, cwd=ROOT,
    )


def test_train_cli_graph():
    out = _run(["repro.launch.train", "graph", "--dataset", "tiny",
                "--clients", "2", "--rounds", "4", "--engine", "direct"])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "best_test=" in out.stdout
    assert "pretrain_comm_scalars=" in out.stdout


def test_train_cli_lm():
    out = _run(["repro.launch.train", "lm", "--arch", "granite-moe-1b-a400m",
                "--reduced", "--steps", "3", "--batch", "2", "--seq-len", "32"])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "loss=" in out.stdout


def test_serve_cli():
    out = _run(["repro.launch.serve", "--arch", "yi-6b", "--reduced",
                "--batch", "2", "--prompt-len", "8", "--gen-len", "4"])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "prefill:" in out.stdout and "decode:" in out.stdout


DRYRUN_RECORD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json, sys

import jax

from repro.configs import INPUT_SHAPES, get_config
from repro.launch.dryrun import run_one

# Reduced configs + scaled-down shapes so CPU compile stays fast; the
# record schema is identical to the production dry-run's.
from repro.launch.mesh import make_debug_mesh
mesh = make_debug_mesh(2, 2)
for arch, shape_name in (("yi-6b", "train_4k"), ("granite-moe-1b-a400m", "decode_32k")):
    shape = dataclasses.replace(INPUT_SHAPES[shape_name], seq_len=64, global_batch=4)
    rec = run_one(arch, shape_name, multi_pod=False,
                  mesh=mesh, cfg=get_config(arch).reduced(), shape=shape)
    print("RECORD " + json.dumps(rec))
"""


def test_dryrun_records_schema():
    """Dry-run records generate end-to-end (reduced configs, (2,2) host
    mesh) and carry the CURRENT record schema: ok status, positive
    roofline/cost terms, serialisable payload. Replaces the old assertion
    over a committed 80-record artifact set that this checkout never had
    (it skipped forever)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(ROOT / "src")
    out = subprocess.run(
        [sys.executable, "-c", DRYRUN_RECORD_SCRIPT], env=env,
        capture_output=True, text=True, timeout=580,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    recs = [json.loads(l.split(" ", 1)[1]) for l in out.stdout.splitlines()
            if l.startswith("RECORD ")]
    assert len(recs) == 2, out.stdout[-2000:]
    for rec in recs:
        assert rec["status"] == "ok", rec.get("error")
        assert rec["mesh"] == "2x2" and rec["chips"] == 4
        assert rec["kind"] in ("train", "decode")
        assert rec["lower_s"] >= 0 and rec["compile_s"] >= 0
        rl = rec["roofline"]
        assert rl["compute_s"] > 0 and rl["memory_s"] > 0
        assert rl["memory_s_hlo_upper"] > 0
        assert rec["hlo_cost"]["flops"] > 0
        assert rec["model_flops_global"] > 0 and rec["model_flops_per_chip"] > 0
        assert rec["active_params"] > 0 and rec["total_params"] > 0
        json.dumps(rec)  # records must stay JSON-serialisable


def test_compile_cache_placement(monkeypatch, tmp_path):
    """Entry points leave a JAX_COMPILATION_CACHE_DIR set from outside to
    JAX, and otherwise keep the cache at a fixed path in the checkout."""
    import jax

    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.configure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = str(ROOT / ".jax_compile_cache")
        assert compile_cache.configure_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
