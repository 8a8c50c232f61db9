"""Share of its roofline that the ``cheb_attn`` Pallas forward reaches:
the least time the chip could take for the graphs the kernel processed in
the window, max(flops / bf16 peak, bytes / HBM bandwidth) from
``bench/work/cheb_attn_fwd.py``, over the summed device time of the
kernel's events (HLO custom calls whose name holds ``cheb_attn``). The
bytes bound it at these shapes."""
import math

from bench import xplane
from bench.work import cheb_attn_fwd

KERNEL = "cheb_attn"


def read(view):
    events = [op for ops in view.trace.ops.values() for op in ops
              if KERNEL in op.name and not xplane.is_container(op.name)]
    if not events:
        return None
    seconds = sum(op.end_ns - op.start_ns for op in events) / 1e9
    model = view.cell.config["program"]["model"]
    n, d = view.graph["features"].shape
    flops, nbytes = cheb_attn_fwd.per_graph(
        n, view.graph["nbr_idx"].shape[1], d, int(model["heads"]), int(model["degree"]))
    # The kernel's result is (H, N, d) for one graph, (G, H, N, d) for G
    # graphs batched over the cohort's lanes.
    graphs = sum(math.prod(xplane.result_dims(op.name)[:-3]) for op in events)
    least = graphs * max(flops / view.peaks["bf16_flops_per_s"],
                         nbytes / view.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
