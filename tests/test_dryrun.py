"""Dry-run machinery on a small host mesh (subprocess: needs forced device
count before jax init). Compiles train/prefill/decode steps for one arch
per family on a (2,2) mesh and checks the analyzer output is sane."""
import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.configs import get_config, INPUT_SHAPES
from repro.launch.steps import build_sharded_step
from repro.analysis.hlo_graph import analyze_hlo
import dataclasses

# reduced configs so CPU compile stays fast; shapes scaled down too
SHAPE = dataclasses.replace(INPUT_SHAPES["train_4k"], seq_len=64, global_batch=4)
DEC = dataclasses.replace(INPUT_SHAPES["decode_32k"], seq_len=128, global_batch=4)

from repro.launch.mesh import make_debug_mesh
mesh = make_debug_mesh(2, 2)
for arch in ("yi-6b", "granite-moe-1b-a400m", "rwkv6-1.6b", "hymba-1.5b",
             "paligemma-3b", "seamless-m4t-large-v2", "chatglm3-6b",
             "dbrx-132b", "qwen2-72b", "minitron-8b"):
    cfg = get_config(arch).reduced()
    for shape in (SHAPE, DEC):
        fn, args, in_sh, out_sh = build_sharded_step(cfg, shape, mesh)
        with mesh:
            compiled = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh).lower(*args).compile()
        cost = analyze_hlo(compiled.as_text())
        assert cost.flops > 0, (arch, shape.name)
        print(f"{arch} {shape.kind} flops={cost.flops:.2e} coll={cost.collective_bytes:.2e}")
print("DRYRUN_OK")
"""


def test_dryrun_small_mesh_all_families():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, capture_output=True, text=True,
        timeout=580,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "DRYRUN_OK" in out.stdout
