"""The command prints no result and exits non-zero where it must not run:
off a TPU, and in a directory that holds only BENCHMARK.json and bench/."""
import json
import os
import shutil
import subprocess
import sys

from bench.tests.conftest import ROOT


def _run(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fedgat-pubmed-k8",
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def _no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            if "metrics" in json.loads(line):
                return False
        except (json.JSONDecodeError, TypeError):
            pass
    return True


def test_refuses_off_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = _run(ROOT, env)
    assert r.returncode != 0 and _no_result(r.stdout), r.stderr[-2000:]
    assert "not a TPU" in r.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = _run(tmp_path, env)
    assert r.returncode != 0 and _no_result(r.stdout), r.stderr[-2000:]


def test_unknown_chip_kind_is_an_error():
    from bench import spec

    try:
        spec.load_peaks("TPU v9 imaginary")
    except KeyError as err:
        assert "not in bench/peaks.json" in str(err)
    else:
        raise AssertionError("an unknown device kind must not get a default")
