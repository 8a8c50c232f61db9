"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips (profiler trace, ``XLA Ops`` line)."""
from bench import xplane


def read(view):
    window = view.trace.window[1] - view.trace.window[0]
    busy = [xplane.busy_ns(ops) / window for ops in view.trace.ops.values()]
    return 100.0 * (1.0 - sum(busy) / len(busy))
