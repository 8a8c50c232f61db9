"""Operations one federated round of FedGAT needs (two-layer GAT, layer 1
the power-series attention): for each selected client and local step a
forward and a backward (3 x forward) over the client's own rows (owned
rows at layer 2, owned plus 1-hop halo rows at layer 1), and one forward
of evaluation over the whole graph."""
import numpy as np


def client_rows(nbr_idx, nbr_mask, owner, client: int):
    """(rows at layer 1, rows at the last layer) of ``client``: its owned
    rows and their 1-hop neighbours, and its owned rows."""
    own = np.nonzero(owner == client)[0]
    need = np.zeros(owner.shape[0], dtype=bool)
    need[own] = True
    need[nbr_idx[own][nbr_mask[own]]] = True
    return int(need.sum()), int(own.size)


def layer1_per_row(d, hidden, heads, b, degree):
    scores = 2 * 2 * d * heads + heads * b                 # b1.h_i, b2.h_j, add
    series = heads * b * (2 * (degree + 1) + 1)            # Horner + mask
    aggregate = heads * b * (2 * d + 1) + heads * d        # sums and divide
    project = 2 * heads * d * hidden + heads * hidden      # W and ELU
    return scores + series + aggregate + project


def layer2_per_row(d2, classes, heads, b):
    proj = 2 * d2 * classes * heads + 2 * 2 * classes * heads
    attend = heads * b * (2 + 4) + 2 * heads * b * classes + classes
    return proj + attend


def flops_per_round(graph, owner, config, job) -> float:
    m = config["program"]["model"]
    n, d = graph["features"].shape
    b = graph["nbr_idx"].shape[1]
    c = int(graph["num_classes"])
    f1 = layer1_per_row(d, int(m["hidden"]), int(m["heads"]), b, int(m["degree"]))
    f2 = layer2_per_row(int(m["hidden"]) * int(m["heads"]), c, int(m["out_heads"]), b)
    k = int(job["num_clients"])
    n_sel = min(k, max(1, int(float(job["client_fraction"]) * k + 0.5)))
    train = 0.0
    for client in range(k):
        rows1, rows2 = client_rows(graph["nbr_idx"], graph["nbr_mask"], owner, client)
        train += 3 * int(job["local_steps"]) * (f1 * rows1 + f2 * rows2)
    return train * n_sel / k + n * (f1 + f2)
