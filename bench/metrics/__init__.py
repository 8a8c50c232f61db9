"""Per-layer metric readers, one module per metric of BENCHMARK.json's
``per_layer`` list, found by the metric's name. Each has
``read(view) -> float | None`` (``view`` is ``bench.harness.RunView``) and
returns None where its run has nothing to read."""
