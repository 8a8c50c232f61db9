"""Operation and byte counts of bench/work against hand counts at the
cells' shapes."""
import numpy as np

from bench.work import cheb_attn_fwd, fedgat_round


def test_cheb_attn_fwd_at_sbm100k():
    # H=8 heads, N=100,000 rows, B=16 slots, d=32 features, degree 16.
    flops, nbytes = cheb_attn_fwd.per_graph(100_000, 16, 32, 8, 16)
    # Per (head, row, slot): 17 Horner multiply-adds (34), the mask (1),
    # the denominator's add (1), 32 multiply-adds (64) = 100; per (head,
    # row): 32 divides. 8 * 1e5 * (16 * 100 + 32).
    assert flops == 8 * 100_000 * 1632 == 1_305_600_000
    # scores 8*1e5*16*4 + h 1e5*32*4 + ids 1e5*16*4 + mask 1e5*16 + out 8*1e5*32*4
    assert nbytes == 51_200_000 + 12_800_000 + 6_400_000 + 1_600_000 + 102_400_000
    # Memory-bound on a v5e: 174.4 MB at 819 GB/s = 213 us > 1.3 GFLOP at 197 TFLOP/s.
    assert nbytes / 819e9 > flops / 197e12


def test_cheb_attn_fwd_at_pubmed():
    # H=8 heads, N=19,717 rows, B=16 slots, d=500 features, degree 16.
    flops, nbytes = cheb_attn_fwd.per_graph(19_717, 16, 500, 8, 16)
    # Per (head, row, slot): 34 + 1 + 1 + 500 multiply-adds (1000) = 1036;
    # per (head, row): 500 divides. 8 * 19,717 * (16 * 1036 + 500).
    assert flops == 8 * 19_717 * 17_076 == 2_693_499_936
    # scores 8*19717*16*4 + h 19717*500*4 + ids 19717*16*4 + mask 19717*16
    # + out 8*19717*500*4
    assert nbytes == 10_095_104 + 39_434_000 + 1_261_888 + 315_472 + 315_472_000
    # 366.6 MB at 819 GB/s = 448 us > 2.7 GFLOP at 197 TFLOP/s = 14 us.
    assert nbytes / 819e9 > flops / 197e12


def _toy_graph():
    # 4 nodes, B=2: node i's slots hold itself and its right neighbour.
    nbr_idx = np.array([[0, 1], [1, 2], [2, 3], [3, 0]], np.int32)
    nbr_mask = np.array([[1, 1], [1, 1], [1, 1], [1, 0]], bool)
    return {"features": np.zeros((4, 3), np.float32), "nbr_idx": nbr_idx,
            "nbr_mask": nbr_mask, "num_classes": 2}


def test_client_rows():
    g = _toy_graph()
    owner = np.array([0, 0, 1, 1])
    # Client 0 owns {0, 1}; their slots reach {0, 1, 2}.
    assert fedgat_round.client_rows(g["nbr_idx"], g["nbr_mask"], owner, 0) == (3, 2)
    # Client 1 owns {2, 3}; node 3's second slot is masked: {2, 3}.
    assert fedgat_round.client_rows(g["nbr_idx"], g["nbr_mask"], owner, 1) == (2, 2)


def test_fedgat_round_by_hand():
    g = _toy_graph()
    owner = np.array([0, 1, 1, 1])
    model = {"hidden": 2, "heads": 3, "out_heads": 1, "degree": 4}
    config = {"program": {"model": model}}
    job = {"num_clients": 2, "client_fraction": 0.5, "local_steps": 1}
    d, hid, h, b, p = 3, 2, 3, 2, 4
    f1 = (4 * d * h + h * b) + h * b * (2 * (p + 1) + 1) + (h * b * (2 * d + 1) + h * d) \
        + (2 * h * d * hid + h * hid)
    f2 = (2 * (hid * h) * 2 * 1 + 4 * 2 * 1) + (1 * b * 6 + 2 * 1 * b * 2 + 2)
    assert fedgat_round.layer1_per_row(d, hid, h, b, p) == f1
    assert fedgat_round.layer2_per_row(hid * h, 2, 1, b) == f2
    rows = [fedgat_round.client_rows(g["nbr_idx"], g["nbr_mask"], owner, k) for k in (0, 1)]
    assert rows == [(2, 1), (3, 3)]
    train = sum(3 * 1 * (f1 * r1 + f2 * r2) for r1, r2 in rows)
    # One of two clients selected per round: half the clients' work.
    assert fedgat_round.flops_per_round(g, owner, config, job) == train / 2 + 4 * (f1 + f2)
