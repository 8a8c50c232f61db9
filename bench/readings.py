#!/usr/bin/env python3
"""Readings that the limits of ``bench/cells/<cell>.json`` are set from:
the compared numbers of sound runs of the program, of the control, and of
each planted fault, on several seeds at a cell's own size and rounds.

    python3 bench/readings.py --workload <cell> --rounds R[,R2...] --seeds 1,2,3 \\
        --what program,control,state_unchanged,half_batch,aggregate_altered

One process; one JSON line per (seed, what) on standard output. The
control is the reference put in the program's place, computed wholly in
bfloat16, the precision below the configuration's float32: parameters,
features, activations, the series and the Adam state (the loss is taken
in float32).
"""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

CONTROL_DTYPE = "bfloat16"


def as_result(run, rounds: int):
    """A reference run after ``rounds`` rounds, in the shape of
    ``Trainer.run``'s result."""
    return {"params": run["params"][rounds], "val_curve": run["val"][:rounds],
            "test_curve": run["test"][:rounds], "partition": _Owner(run["owner"])}


def control_result(cell, arrays, seed: int, rounds: int):
    """The control put in the program's place: the reference's job computed
    in CONTROL_DTYPE, in the shape of ``Trainer.run``'s result."""
    import jax.numpy as jnp

    from bench.reference import federated

    ctl = federated.run(cell.config, cell.job, arrays, seed, rounds,
                        dtype=getattr(jnp, CONTROL_DTYPE))
    return as_result(ctl, rounds)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rounds", required=True,
                    help="comma-separated round counts, each read after its own job")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="program,control")
    args = ap.parse_args(argv)

    from bench import compare, faults, harness, spec

    cell = spec.load_cell(args.workload)
    jax = harness.configure_jax()
    sys.path.insert(0, str(spec.ROOT / "src"))
    import jax.numpy as jnp

    from bench.reference import federated

    what = args.what.split(",")
    counts = [int(r) for r in args.rounds.split(",")]
    print(json.dumps({"device": str(jax.devices()[0].device_kind), "rounds": counts}), flush=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        arrays, g = harness.build_graph(cell.config, seed)
        t = time.perf_counter()
        ref = federated.run(cell.config, cell.job, arrays, seed, max(counts))
        t_ref = time.perf_counter() - t
        ctl = None
        if "control" in what:
            t = time.perf_counter()
            ctl = federated.run(cell.config, cell.job, arrays, seed, max(counts),
                                dtype=getattr(jnp, CONTROL_DTYPE))
            t_ctl = time.perf_counter() - t
        for rounds in counts:
            for w in what:
                t = time.perf_counter()
                if w == "control":
                    result = as_result(ctl, rounds)
                elif w == "program":
                    result = harness.run_job(cell, g, seed, rounds)
                else:
                    with faults.planted(w):
                        result = harness.run_job(cell, g, seed, rounds)
                seconds = t_ctl if w == "control" else time.perf_counter() - t
                nums = compare.numbers(result, ref, rounds)
                print(json.dumps({"seed": seed, "rounds": rounds, "what": w, "seconds": seconds,
                                  "reference_seconds": t_ref, "numbers": nums}), flush=True)
    return 0


class _Owner:
    def __init__(self, owner):
        self.owner = owner


if __name__ == "__main__":
    sys.exit(main())
