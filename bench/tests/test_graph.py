"""The generator's counts, and the reference's semantic-graph build
against the program's."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import graph, sgb_ref

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def _cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_published_counts_are_exact():
    spec = _cfg("han-dblp")["graph"]
    g = graph.make_graph(spec)
    for t, v in spec["nodes"].items():
        assert g["num_nodes"][t] == v["count"]
    for r in spec["relations"]:
        src, dst = g["edges"][r["name"]]
        assert len(src) == r["edges"]
        assert len(set(zip(src.tolist(), dst.tolist()))) == r["edges"]  # simple
    # one venue per paper
    assert np.array_equal(np.sort(g["edges"]["PV"][0]), np.arange(14328))
    assert g["features"]["paper"].shape == (14328, 4231)
    assert g["features"]["venue"].shape == (20, 20)
    assert "generated/published: author 4057/4057" in graph.count_report(g, spec)


def test_graph_is_fixed_by_the_data_seed():
    spec = _cfg("han-dblp")["graph"]
    a, b = graph.make_graph(spec, scale=0.05), graph.make_graph(spec, scale=0.05)
    for name in a["edges"]:
        assert all(np.array_equal(x, y) for x, y in zip(a["edges"][name], b["edges"][name]))
    assert np.array_equal(a["features"]["term"], b["features"]["term"])


def _program_graph(g):
    from repro.core import hetgraph

    return hetgraph.HetGraph(
        node_types=g["node_types"], num_nodes=dict(g["num_nodes"]),
        features=g["features"], relations=g["relations"], edges=g["edges"],
        label_type=g["label_type"], labels=g["labels"], num_classes=g["num_classes"],
    )


@pytest.mark.parametrize("name", ["han-dblp", "simplehgn-dblp", "simplehgn-acm"])
def test_reference_build_matches_the_program(name):
    from repro.core import hetgraph

    cfg = _cfg(name)
    g = graph.make_graph(cfg["graph"], scale=0.1)
    hg = _program_graph(g)
    seed = cfg["graph"]["data_seed"]
    if cfg["model"] == "han":
        mps = {k: tuple(v) for k, v in cfg["metapaths"].items()}
        prog = hetgraph.build_metapath_graphs(hg, mps, max_degree=cfg["max_degree"],
                                              seed=seed, bucket_sizes=(8, 32, 128))
        ref = sgb_ref.metapath_graphs(g, mps, cfg["max_degree"],
                                      cfg["metapath_fanout_cap"], seed)
    else:
        prog = list(hetgraph.build_union_graph(hg, max_degree=cfg["max_degree"], seed=seed,
                                               bucket_sizes=(8, 32, 128)).values())
        ref = sgb_ref.union_graphs(g, cfg["max_degree"], seed)
    assert [sg.name for sg in prog] == list(ref)
    for sg in prog:
        _, nbr, msk, ety = ref[sg.name]
        assert np.array_equal(sg.nbr_mask, msk)
        assert np.array_equal(np.where(msk, sg.nbr_idx, 0), np.where(msk, nbr, 0))
        assert np.array_equal(np.where(msk, sg.edge_type, 0), np.where(msk, ety, 0))
