"""The trace->metric reduction on short traces recorded on a TPU v5e and
committed (``data/<cell>.xplane.pb.gz``, with what that run printed and
its cell's shapes in ``data/<cell>.json``): the reduction must give the same busy time, window
and per-layer metrics again, and its busy time must agree with an
independent count of the same events."""
import gzip
import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from bench import harness, spec, xplane

DATA = Path(__file__).resolve().parent / "data"
RECORDED = sorted(DATA.glob("*.json"))


def _load(tmp_path, rec):
    src = DATA / rec["trace"]
    dst = tmp_path / "t.xplane.pb"
    with gzip.open(src, "rb") as f, open(dst, "wb") as g:
        shutil.copyfileobj(f, g)
    return xplane.load(str(dst))


def _view(rec, trace):
    """The run's view, its cell's shapes as the record keeps them."""
    cell = spec.Cell(name=rec["cell"], chips=1, config=rec["config"], traffic={},
                     limits={}, run_seconds=0, end_to_end=[], per_layer=[])
    n, d = rec["config"]["graph"]["nodes"], rec["config"]["graph"]["features"]
    graph = {"features": np.zeros((n, d), np.float32),
             "nbr_idx": np.zeros((n, rec["padded_degree"]), np.int32)}
    return harness.RunView(
        cell=cell, peaks=spec.load_peaks(rec["device_kind"]), chips=1,
        rounds=rec["rounds"], wall_s=rec["window_s"], trace=trace, spans=[],
        marker_perf_ns=0, graph=graph, results=[],
    )


@pytest.mark.parametrize("path", RECORDED, ids=[p.stem for p in RECORDED])
def test_reduction_repeats_the_chip_run(path, tmp_path):
    rec = json.loads(path.read_text())
    trace = _load(tmp_path, rec)
    busy = sum(xplane.busy_ns(o) for o in trace.ops.values()) / len(trace.ops) / 1e9
    assert busy == pytest.approx(rec["busy_s"], rel=1e-12)
    assert trace.window_s == pytest.approx(rec["window_s"], rel=1e-12)
    view = _view(rec, trace)
    from bench.metrics import device_idle_share

    assert device_idle_share.read(view) == pytest.approx(rec["device_idle_share"], rel=1e-12)
    if "cheb_attn_fwd_roofline" in rec:
        from bench.metrics import cheb_attn_fwd_roofline

        assert cheb_attn_fwd_roofline.read(view) == pytest.approx(
            rec["cheb_attn_fwd_roofline"], rel=1e-12)


@pytest.mark.parametrize("path", RECORDED, ids=[p.stem for p in RECORDED])
def test_busy_time_by_an_independent_count(path, tmp_path):
    """Union length by sweeping +1/-1 events over the sorted boundaries."""
    rec = json.loads(path.read_text())
    trace = _load(tmp_path, rec)
    for ops in trace.ops.values():
        pts = np.array([(o.start_ns, 1) for o in ops] + [(o.end_ns, -1) for o in ops])
        order = np.lexsort((-pts[:, 1], pts[:, 0]))       # starts before ends at a tie
        t, d = pts[order, 0], pts[order, 1]
        depth = np.cumsum(d)
        busy = int(np.sum(np.diff(t)[depth[:-1] > 0]))
        assert busy == xplane.busy_ns(ops)
        assert 0 < busy <= trace.window[1] - trace.window[0]
