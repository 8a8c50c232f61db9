"""A cell of the benchmark cut to a size the CPU runs in seconds: the
cell's own configuration, traffic and limits, on a graph of ``nodes``
nodes (same generator, same degrees and training nodes, the other splits
scaled down)."""
import json

from bench import spec

NODES = 2000


def small_cell(name: str, nodes: int = NODES, rounds: int = 0) -> spec.Cell:
    """The cell at ``nodes`` nodes; ``rounds`` > 0 also shortens its jobs."""
    cell = spec.load_cell(name)
    config = json.loads(json.dumps(cell.config))
    traffic = dict(cell.traffic, rounds=rounds or cell.traffic["rounds"])
    graph = config["graph"]
    graph.update(nodes=nodes, val=nodes // 10, test=nodes // 5,
                 train_per_class=min(graph["train_per_class"], nodes // (4 * graph["classes"])))
    return spec.Cell(name=cell.name, chips=1, config=config, traffic=traffic,
                     limits=cell.limits, run_seconds=cell.run_seconds, end_to_end=cell.end_to_end,
                     per_layer=cell.per_layer)


class CpuPeaks(dict):
    """Peak row for the CPU stand-in: the tests read no device metric."""


def run_small(cell: spec.Cell, seed: int, seconds: float = 1.0):
    """One run through the harness on the CPU, the chip check skipped."""
    import time

    import jax

    from bench import harness

    harness.configure_jax()
    # Compiles of a test stay out of the checkout's cache.
    jax.config.update("jax_enable_compilation_cache", False)
    return harness.execute(
        cell, seed=seed, seconds=seconds, traced=False, devices=jax.devices()[:1],
        peaks=CpuPeaks(bf16_flops_per_s=1.0, hbm_bytes_per_s=1.0),
        t_start=time.perf_counter(),
    )
