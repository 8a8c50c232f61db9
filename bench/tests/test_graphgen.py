"""bench.graphgen is a copy of the program's SBM generator: the same seed
and preset numbers give the same arrays. (sbm_100k itself is compared
once, by hand, as PERF.md records; sbm_10k keeps the test short.)"""
import numpy as np
import pytest

from bench import graphgen


@pytest.mark.parametrize("name,seed", [("sbm_1k", 0), ("sbm_10k", 2**31 + 17)])
def test_matches_make_sbm(name, seed):
    from repro.graphs.synthetic import SBM_PRESETS, make_sbm

    n, d, c, di, do, keep, noise, tr, va, te, cap = SBM_PRESETS[name]
    spec = dict(nodes=n, features=d, classes=c, avg_deg_in=di, avg_deg_out=do,
                keep=keep, noise=noise, train_per_class=tr, val=va, test=te,
                degree_cap=cap, pad_multiple=8)
    ours = graphgen.make_sbm(spec, seed)
    theirs = make_sbm(name, seed)
    for field in theirs._fields:
        a, b = ours[field], getattr(theirs, field)
        if field == "num_classes":
            assert a == b
        else:
            assert a.dtype == b.dtype and np.array_equal(a, b), field
