"""The K1/K2 operation and byte model against hand counts."""
import numpy as np

from pathlib import Path

from bench import costs

ROOT = Path(__file__).resolve().parents[2]


def _adapter(model):
    from bench import run

    return run.load_module(ROOT / "bench" / "models" / f"{model}.py")


def test_na_costs_hand_count():
    # three targets with 2, 5 and 0 real in-edges, K = 3, 2 heads of 4 dims
    c = costs.na_costs(np.array([2, 5, 0]), k=3, heads=2, head_dim=4)
    e, kept, t, h, row = 7, 2 + 3 + 0, 3, 2, 8
    assert c["edges"] == e and c["kept"] == kept and c["targets"] == t
    # K1: per edge θ per head, mask and id read; θ_*v per target; α and id
    # written per kept slot
    assert c["k1_bytes"] == 4 * (e * (h + 2) + t * h + kept * (h + 1))
    assert c["k1_ops"] == e * (h + 1) + kept * h * 5
    # K2: kept rows and their α read, one row written per target
    assert c["k2_bytes"] == 4 * (kept * (row + h) + t * row)
    assert c["k2_ops"] == 2 * kept * row


def test_costs_ignore_padding():
    # the same real edges in a wider padded table cost the same
    m1 = np.zeros((4, 8), bool)
    m1[:, :3] = True
    m2 = np.zeros((4, 256), bool)
    m2[:, :3] = True
    a = costs.na_costs(m1.sum(1), 8, 8, 8)
    b = costs.na_costs(m2.sum(1), 8, 8, 8)
    assert a == b


def test_roofline_share_names_the_bound():
    peaks = {"flops_per_s": 100.0, "bytes_per_s": 10.0}
    share, bound = costs.roofline_share(ops=100.0, nbytes=50.0, seconds=10.0, peaks=peaks)
    assert bound == "bytes" and share == 100.0 * 5.0 / 10.0
    share, bound = costs.roofline_share(ops=1000.0, nbytes=1.0, seconds=20.0, peaks=peaks)
    assert bound == "ops" and share == 50.0


def test_forward_costs_han_counts_every_metapath():
    g = {"num_nodes": {"a": 3, "p": 2}, "features": {"a": np.zeros((3, 5)), "p": np.zeros((2, 7))},
         "num_classes": 2, "label_type": "a", "node_types": ("a", "p")}
    m = np.array([[1, 1, 0], [1, 0, 0], [1, 1, 1]], bool)
    sgs = {"X": ("a", None, m, None), "Y": ("a", None, m, None)}
    cfg = {"heads": 2, "head_dim": 2, "prune_k": 2, "semantic_attention_dim": 3}
    c = costs.forward_costs(_adapter("han"), g, sgs, cfg)
    one = costs.na_costs(m.sum(1), 2, 2, 2)
    assert c["k2_ops"] == 2 * one["k2_ops"] and c["k1_bytes"] == 2 * one["k1_bytes"]
    dim = 4
    flops = 2 * 3 * 5 * dim + 2 * 2 * 2 * 3 * dim + 2 * 3 * (2 * dim * 3 + 2 * dim) + 2 * 3 * dim * 2
    assert c["flops"] == flops + c["k1_ops"] + c["k2_ops"]


def test_forward_costs_simplehgn_runs_the_label_graph_in_the_last_layer():
    g = {"num_nodes": {"a": 3, "p": 2}, "features": {"a": np.zeros((3, 5)), "p": np.zeros((2, 7))},
         "num_classes": 2, "label_type": "a", "node_types": ("a", "p")}
    ma = np.array([[1, 1, 0], [1, 0, 0], [1, 1, 1]], bool)
    mp = np.array([[1, 0, 0], [1, 1, 0]], bool)
    sgs = {"union:a": ("a", None, ma, None), "union:p": ("p", None, mp, None)}
    cfg = {"heads": 2, "head_dim": 2, "prune_k": 2, "num_layers": 2}
    c = costs.forward_costs(_adapter("simplehgn"), g, sgs, cfg)
    a, p = costs.na_costs(ma.sum(1), 2, 2, 2), costs.na_costs(mp.sum(1), 2, 2, 2)
    # layer 1 runs both graphs, layer 2 the label type's only
    assert c["k2_ops"] == 2 * a["k2_ops"] + p["k2_ops"]
    assert c["k1_bytes"] == 2 * a["k1_bytes"] + p["k1_bytes"]
    dim = 4
    layer1 = sum(2 * (2 * n * f * dim + 2 * n * dim) for n, f in ((3, 5), (2, 7)))
    layer2 = sum(2 * n * dim * dim + 2 * n * dim for n in (3, 2)) + 2 * 3 * dim * dim + 2 * 3 * dim
    assert c["flops"] == layer1 + layer2 + 2 * 3 * dim * 2 + c["k1_ops"] + c["k2_ops"]
