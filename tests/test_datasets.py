"""Dataset ingestion subsystem: on-disk dump round-trips, the SGB artifact
cache, the vectorized synthetic edge generator, split guarantees, and
schema validation / malformed-dump rejection.

The loop-based `_bipartite_edges` golden reference lives in
benchmarks/sgb_scale.py (it doubles as the gen-speedup baseline there);
importing it keeps the oracle and the benchmark baseline from drifting.
"""
import json

import numpy as np
import pytest

from benchmarks.sgb_scale import _bipartite_edges_loop
from repro.core import hetgraph, pipeline
from repro.core.flows import FlowConfig
from repro.data import datasets, sgb_cache, synthetic


# --------------------------------------------------------------------------
# on-disk round-trip
# --------------------------------------------------------------------------

def _assert_graph_equal(a, b):
    assert a.node_types == b.node_types
    assert a.num_nodes == b.num_nodes
    assert a.relations == b.relations
    assert a.label_type == b.label_type
    assert a.num_classes == b.num_classes
    np.testing.assert_array_equal(a.labels, b.labels)
    for rel in a.edges:
        np.testing.assert_array_equal(a.edges[rel][0], b.edges[rel][0])
        np.testing.assert_array_equal(a.edges[rel][1], b.edges[rel][1])
    for t in a.node_types:
        np.testing.assert_array_equal(a.features[t], b.features[t])


@pytest.mark.parametrize("edge_format", ["npz", "csv"])
def test_roundtrip_bit_identical(tmp_path, edge_format):
    g = synthetic.make_acm(scale=0.04, seed=0)
    datasets.save_hetgraph(
        g, tmp_path / "acm", name="acm",
        metapaths=synthetic.METAPATHS["acm"], edge_format=edge_format,
    )
    g2 = datasets.load_hetgraph(tmp_path / "acm")
    _assert_graph_equal(g, g2)
    meta = datasets.read_meta(tmp_path / "acm")
    assert meta["metapaths"] == {
        k: list(v) for k, v in synthetic.METAPATHS["acm"].items()
    }


def test_roundtrip_csv_features(tmp_path):
    g = synthetic.make_imdb(scale=0.03, seed=1)
    datasets.save_hetgraph(g, tmp_path / "d", feature_format="csv",
                           edge_format="csv")
    g2 = datasets.load_hetgraph(tmp_path / "d")
    _assert_graph_equal(g, g2)  # %.9e repr-roundtrips float32 exactly


def test_reexport_other_format_not_shadowed(tmp_path):
    """Re-exporting a different graph in the other format into the same
    directory must not leave the first export's files shadowing it: the
    loader honors meta.json's recorded formats and the writer removes the
    other format's files."""
    g1 = synthetic.make_acm(scale=0.03, seed=0)
    g2 = synthetic.make_acm(scale=0.03, seed=5)  # different edges
    d = tmp_path / "d"
    datasets.save_hetgraph(g1, d, edge_format="npz", feature_format="npz")
    datasets.save_hetgraph(g2, d, edge_format="csv", feature_format="csv")
    _assert_graph_equal(datasets.load_hetgraph(d), g2)
    assert not (d / "edges.npz").exists()
    assert not (d / "features.npz").exists()
    # and back again: npz over csv
    datasets.save_hetgraph(g1, d, edge_format="npz", feature_format="npz")
    _assert_graph_equal(datasets.load_hetgraph(d), g1)
    assert not (d / "edges").exists() and not (d / "features").exists()
    # meta's recorded format wins even over a stray leftover file
    datasets.save_hetgraph(g2, d, edge_format="csv")
    (d / "edges.npz").write_bytes(b"junk")  # stray file, meta says csv
    _assert_graph_equal(datasets.load_hetgraph(d), g2)


@pytest.mark.parametrize("model", ["han", "rgat", "simple_hgn"])
def test_prepare_from_path_matches_registry(tmp_path, model):
    """pipeline.prepare accepts a registry name and an on-disk dump path
    interchangeably: identical HetGraph -> identical bucketed layouts ->
    bit-identical logits."""
    g, name, mps = datasets.resolve("acm", scale=0.04, seed=0)
    datasets.save_hetgraph(g, tmp_path / "acm", name="acm", metapaths=mps)
    a = pipeline.prepare(model, "acm", scale=0.04, max_degree=32, seed=0)
    b = pipeline.prepare(model, str(tmp_path / "acm"), max_degree=32, seed=0)
    assert b.name == f"{model}/acm"
    for sa, sb in zip(a.sgs, b.sgs):
        assert sa.name == sb.name
        np.testing.assert_array_equal(sa.nbr_idx, sb.nbr_idx)
        np.testing.assert_array_equal(sa.nbr_mask, sb.nbr_mask)
    for flow in ("staged", "fused"):
        la = np.asarray(a.logits(a.params, FlowConfig(flow, prune_k=4)))
        lb = np.asarray(b.logits(b.params, FlowConfig(flow, prune_k=4)))
        np.testing.assert_array_equal(la, lb)


def test_resolve_hetgraph_passthrough_and_unknown():
    g = synthetic.make_acm(scale=0.03)
    g2, name, mps = datasets.resolve(g)
    assert g2 is g and mps is None
    with pytest.raises(ValueError, match="unknown dataset"):
        datasets.resolve("no_such_dataset")


def test_prepare_han_from_hetgraph_with_metapaths():
    """An in-memory HetGraph carries no metapath table; prepare(metapaths=)
    supplies one — logits match the registry build bit-for-bit."""
    g, _, mps = datasets.resolve("acm", scale=0.04, seed=0)
    a = pipeline.prepare("han", "acm", scale=0.04, max_degree=32, seed=0)
    b = pipeline.prepare("han", g, max_degree=32, seed=0, metapaths=mps)
    cfg = FlowConfig("fused", prune_k=4)
    np.testing.assert_array_equal(
        np.asarray(a.logits(a.params, cfg)), np.asarray(b.logits(b.params, cfg))
    )
    with pytest.raises(ValueError, match="needs metapaths"):
        pipeline.prepare("han", g, max_degree=32, seed=0)


def test_resolve_registry_dump_collision(tmp_path, monkeypatch):
    """A dump directory whose relative name collides with a registry name
    must fail loud, not silently resolve to the synthetic generator; an
    explicit path prefix disambiguates."""
    g = synthetic.make_acm(scale=0.03, seed=0)
    datasets.save_hetgraph(g, tmp_path / "acm", name="acm-dump")
    monkeypatch.chdir(tmp_path)
    with pytest.raises(ValueError, match="both a registered generator"):
        datasets.resolve("acm")
    g2, name, _ = datasets.resolve("./acm")  # explicit path: the dump
    assert name == "acm-dump"
    _assert_graph_equal(g, g2)


# --------------------------------------------------------------------------
# SGB artifact cache
# --------------------------------------------------------------------------

@pytest.fixture()
def small_graph():
    return synthetic.make_acm(scale=0.05, seed=0)


def test_cache_miss_then_hit_identical_layouts(tmp_path, small_graph):
    kw = dict(max_degree=32, seed=0, bucket_sizes=(4, 8, 16),
              cache_dir=tmp_path, shards=2)
    built, st1 = sgb_cache.build_or_load(small_graph, "relation", **kw)
    assert st1 == "miss"
    assert list(tmp_path.glob("sgb_*.npz"))
    loaded, st2 = sgb_cache.build_or_load(small_graph, "relation", **kw)
    assert st2 == "hit"
    for a, b in zip(built, loaded):
        tt, w = sgb_cache._tile_shape(a)
        assert a.name == b.name and a.src_types == b.src_types
        assert a.dst_type == b.dst_type
        assert a.num_edge_types == b.num_edge_types
        assert len(a.buckets) == len(b.buckets)
        for ba, bb in zip(a.buckets, b.buckets):
            np.testing.assert_array_equal(ba.targets, bb.targets)
            np.testing.assert_array_equal(ba.nbr_idx, bb.nbr_idx)
            np.testing.assert_array_equal(ba.nbr_mask, bb.nbr_mask)
            np.testing.assert_array_equal(ba.edge_type, bb.edge_type)
        np.testing.assert_array_equal(a.target_perm(), b.target_perm())
        # the grouped layout was injected, not rebuilt, and is identical
        assert (tt, w) in b._grouped
        la, lb = a.grouped(tt, w), b.grouped(tt, w)
        for f in ("nbr", "msk", "ety", "step_row", "step_dt", "step_ndt",
                  "step_bucket", "caps", "caps_pad", "row_targets", "perm"):
            np.testing.assert_array_equal(getattr(la, f), getattr(lb, f))
        assert la.num_rows == lb.num_rows
        # the sharded split too
        assert (2, tt, w) in b._sharded
        sa, sb = a.sharded(2, tt, w), b.sharded(2, tt, w)
        np.testing.assert_array_equal(sa.perm, sb.perm)
        assert sa.num_rows_alloc == sb.num_rows_alloc
        assert sa.num_steps_max == sb.num_steps_max
        for ga, gb in zip(sa.shards, sb.shards):
            np.testing.assert_array_equal(ga.nbr, gb.nbr)
            np.testing.assert_array_equal(ga.perm, gb.perm)
            np.testing.assert_array_equal(ga.step_row, gb.step_row)


def test_cache_union_dict_roundtrip(tmp_path, small_graph):
    kw = dict(max_degree=16, seed=0, bucket_sizes=(4, 8),
              cache_dir=tmp_path)
    built, st1 = sgb_cache.build_or_load(small_graph, "union", **kw)
    loaded, st2 = sgb_cache.build_or_load(small_graph, "union", **kw)
    assert (st1, st2) == ("miss", "hit")
    assert isinstance(loaded, dict) and list(loaded) == list(built)
    for k in built:
        np.testing.assert_array_equal(built[k].nbr_idx, loaded[k].nbr_idx)
        np.testing.assert_array_equal(built[k].edge_type, loaded[k].edge_type)


def test_cache_key_invalidation(tmp_path, small_graph):
    base = dict(max_degree=32, seed=0, bucket_sizes=(4, 8, 16),
                cache_dir=tmp_path)
    _, st = sgb_cache.build_or_load(small_graph, "relation", **base)
    assert st == "miss"
    # same args: hit
    _, st = sgb_cache.build_or_load(small_graph, "relation", **base)
    assert st == "hit"
    # bucket_sizes changes the key
    _, st = sgb_cache.build_or_load(
        small_graph, "relation", **{**base, "bucket_sizes": (8, 16)}
    )
    assert st == "miss"
    # max_degree changes the key
    _, st = sgb_cache.build_or_load(
        small_graph, "relation", **{**base, "max_degree": 64}
    )
    assert st == "miss"
    # graph structure changes the key (drop one edge)
    g2 = synthetic.make_acm(scale=0.05, seed=0)
    rel = g2.relations[0][1]
    s, d = g2.edges[rel]
    g2.edges[rel] = (s[:-1], d[:-1])
    _, st = sgb_cache.build_or_load(g2, "relation", **base)
    assert st == "miss"
    # features do NOT change the key (SGB never reads them)
    g3 = synthetic.make_acm(scale=0.05, seed=0)
    g3.features[g3.node_types[0]] = g3.features[g3.node_types[0]] + 1.0
    _, st = sgb_cache.build_or_load(g3, "relation", **base)
    assert st == "hit"


def test_cache_hit_upgrades_with_missing_shard_split(tmp_path, small_graph):
    """An entry warmed without a mesh split gains one on the first hit that
    needs it (status stays "hit"), and the upgraded entry serves every
    later process precomputed — alongside any splits it already had."""
    kw = dict(max_degree=32, seed=0, bucket_sizes=(4, 8, 16),
              cache_dir=tmp_path)
    shape = sgb_cache._tile_shape
    _, st = sgb_cache.build_or_load(small_graph, "relation", **kw)
    assert st == "miss"
    up, st = sgb_cache.build_or_load(small_graph, "relation", shards=4, **kw)
    assert st == "hit"
    assert all((4, *shape(sg)) in sg._sharded for sg in up)
    # a fresh load now carries the 4-way split without rebuilding it
    loaded, _ = sgb_cache.load_sgb(next(tmp_path.glob("sgb_*.npz")))
    assert all((4, *shape(sg)) in sg._sharded for sg in loaded)
    # asking for a second mesh size keeps the first in the entry
    sgb_cache.build_or_load(small_graph, "relation", shards=2, **kw)
    loaded, _ = sgb_cache.load_sgb(next(tmp_path.glob("sgb_*.npz")))
    for sg in loaded:
        tt, w = shape(sg)
        assert (2, tt, w) in sg._sharded and (4, tt, w) in sg._sharded
        for n in (2, 4):
            fresh = hetgraph.shard_layout(sg.grouped(tt, w), n)
            np.testing.assert_array_equal(
                sg._sharded[(n, tt, w)].perm, fresh.perm
            )


def test_cache_flat_layout_not_cached(tmp_path, small_graph):
    out, st = sgb_cache.build_or_load(
        small_graph, "relation", max_degree=32, seed=0, bucket_sizes=None,
        cache_dir=tmp_path,
    )
    assert st == "off" and not list(tmp_path.glob("sgb_*.npz"))
    assert all(isinstance(sg, hetgraph.SemanticGraph) for sg in out)


def test_cache_env_var_activates(tmp_path, small_graph, monkeypatch):
    """$REPRO_SGB_CACHE is the ambient opt-in: with no explicit cache_dir
    the cache is off, with the variable set it is active."""
    kw = dict(max_degree=32, seed=0, bucket_sizes=(4, 8))
    monkeypatch.delenv("REPRO_SGB_CACHE", raising=False)
    _, st = sgb_cache.build_or_load(small_graph, "relation", **kw)
    assert st == "off"
    monkeypatch.setenv("REPRO_SGB_CACHE", str(tmp_path / "amb"))
    _, st = sgb_cache.build_or_load(small_graph, "relation", **kw)
    assert st == "miss"
    _, st = sgb_cache.build_or_load(small_graph, "relation", **kw)
    assert st == "hit"
    assert list((tmp_path / "amb").glob("sgb_*.npz"))


def test_cache_corrupt_entry_rebuilt(tmp_path, small_graph):
    kw = dict(max_degree=32, seed=0, bucket_sizes=(4, 8), cache_dir=tmp_path)
    sgb_cache.build_or_load(small_graph, "relation", **kw)
    (entry,) = tmp_path.glob("sgb_*.npz")
    entry.write_bytes(b"not an npz")
    out, st = sgb_cache.build_or_load(small_graph, "relation", **kw)
    assert st == "miss"  # torn entry: rebuilt and overwritten
    out2, st2 = sgb_cache.build_or_load(small_graph, "relation", **kw)
    assert st2 == "hit"
    np.testing.assert_array_equal(out[0].nbr_idx, out2[0].nbr_idx)


def test_prepare_cached_logits_identical(tmp_path):
    """prepare() through the cache (miss, then hit in a fresh prepare) is
    logits-identical to the uncached build under every flow."""
    for model in ("han", "rgat", "simple_hgn"):
        plain = pipeline.prepare(model, "acm", scale=0.04, max_degree=32,
                                 seed=0)
        cold = pipeline.prepare(model, "acm", scale=0.04, max_degree=32,
                                seed=0, sgb_cache_dir=tmp_path)
        warm = pipeline.prepare(model, "acm", scale=0.04, max_degree=32,
                                seed=0, sgb_cache_dir=tmp_path)
        cfg = FlowConfig("fused", prune_k=4)
        lp = np.asarray(plain.logits(plain.params, cfg))
        lc = np.asarray(cold.logits(cold.params, cfg))
        lw = np.asarray(warm.logits(warm.params, cfg))
        np.testing.assert_array_equal(lp, lc)
        np.testing.assert_array_equal(lp, lw)


# --------------------------------------------------------------------------
# vectorized edge generator: golden stats vs the loop reference
# --------------------------------------------------------------------------

def _gen_pair(seed, n_src=900, n_dst=700, mean_deg=4.0, noise=0.15,
              n_comm=3):
    rng = np.random.default_rng(seed)
    comm_src = rng.integers(0, n_comm, size=n_src)
    comm_dst = rng.integers(0, n_comm, size=n_dst)
    args = (n_src, n_dst, mean_deg, comm_src, comm_dst, noise)
    vec = synthetic._bipartite_edges(np.random.default_rng(seed), *args)
    ref = _bipartite_edges_loop(np.random.default_rng(seed), *args)
    return vec, ref, comm_src, comm_dst


@pytest.mark.parametrize("seed", range(5))
def test_generator_matches_loop_stats(seed):
    """Same degree model, same dedup semantics: the vectorized draw and the
    loop draw consume the SAME rng stream for the degree vector, so the
    per-target degree histogram matches exactly up to dedup losses; source
    community structure matches within sampling tolerance."""
    (vs, vd), (rs, rd), comm_src, comm_dst = _gen_pair(seed)
    # edge counts within a few percent (dedup losses differ slightly)
    assert abs(len(vs) - len(rs)) / len(rs) < 0.05
    # identical pre-dedup target degree draw -> close post-dedup histograms
    hv = np.bincount(vd, minlength=700)
    hr = np.bincount(rd, minlength=700)
    assert abs(hv.sum() - hr.sum()) / hr.sum() < 0.05
    assert abs(int(hv.max()) - int(hr.max())) <= max(2, 0.2 * hr.max())
    # heavy tail survives: same p99 within tolerance
    assert abs(np.percentile(hv, 99) - np.percentile(hr, 99)) <= 3
    # community assortativity: intra-community edge fraction within 3%
    intra_v = (comm_src[vs] == comm_dst[vd]).mean()
    intra_r = (comm_src[rs] == comm_dst[rd]).mean()
    assert abs(intra_v - intra_r) < 0.03
    # dedup semantics: no duplicate (src, dst) pairs, sorted by key
    key = vs * 700 + vd
    assert len(np.unique(key)) == len(key)


def test_generator_seed_stable():
    """Deterministic per (seed, scale) — the contract SGB cache keys and
    released-version reproducibility rest on."""
    a = synthetic.make_dblp(scale=0.05, seed=7)
    b = synthetic.make_dblp(scale=0.05, seed=7)
    for rel in a.edges:
        np.testing.assert_array_equal(a.edges[rel][0], b.edges[rel][0])
        np.testing.assert_array_equal(a.edges[rel][1], b.edges[rel][1])
    c = synthetic.make_dblp(scale=0.05, seed=8)
    assert any(
        not np.array_equal(a.edges[r][0], c.edges[r][0]) for r in a.edges
    )


def test_generator_empty_community_pool():
    """A destination whose community has no sources falls back to uniform
    picks instead of crashing (the loop's semantics)."""
    rng = np.random.default_rng(0)
    comm_src = np.zeros(50, np.int64)  # only community 0 has sources
    comm_dst = np.full(30, 1, np.int64)  # all dsts in community 1
    s, d = synthetic._bipartite_edges(rng, 50, 30, 3.0, comm_src, comm_dst,
                                      0.1)
    assert len(s) > 0 and s.max() < 50 and d.max() < 30


# --------------------------------------------------------------------------
# pipeline._splits: non-empty + disjoint-union coverage
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n", [3, 4, 5, 6, 9, 10, 50, 1000])
def test_splits_nonempty_and_cover(n):
    sp = pipeline._splits(n, seed=0)
    assert set(sp) == {"train", "val", "test"}
    for k, v in sp.items():
        assert len(v) > 0, (n, k)
    allv = np.concatenate([sp["train"], sp["val"], sp["test"]])
    np.testing.assert_array_equal(np.sort(allv), np.arange(n))


def test_splits_large_fractions_unchanged():
    sp = pipeline._splits(100, seed=0)
    assert len(sp["train"]) == 60 and len(sp["val"]) == 20
    assert len(sp["test"]) == 20


# --------------------------------------------------------------------------
# HetGraph.validate + malformed-dump rejection
# --------------------------------------------------------------------------

def _tiny_graph():
    return hetgraph.HetGraph(
        node_types=("a", "b"),
        num_nodes={"a": 4, "b": 3},
        features={"a": np.zeros((4, 2), np.float32),
                  "b": np.zeros((3, 2), np.float32)},
        relations=(("a", "AB", "b"),),
        edges={"AB": (np.array([0, 1, 3]), np.array([0, 2, 1]))},
        label_type="b",
        labels=np.array([0, 1, 0], np.int32),
        num_classes=2,
    )


def test_validate_ok():
    assert _tiny_graph().validate() is not None


def test_validate_out_of_range_edges():
    g = _tiny_graph()
    g.edges["AB"] = (np.array([0, 9]), np.array([0, 1]))
    with pytest.raises(ValueError, match="src ids .* out of range"):
        g.validate()


def test_validate_label_and_feature_mismatch():
    g = _tiny_graph()
    g.labels = np.array([0, 1], np.int32)  # 2 rows for 3 nodes
    g.features["a"] = np.zeros((5, 2), np.float32)
    with pytest.raises(ValueError) as e:
        g.validate()
    msg = str(e.value)
    assert "labels rows" in msg and "features['a']" in msg


def test_validate_duplicate_relations():
    g = _tiny_graph()
    g.relations = (("a", "AB", "b"), ("b", "AB", "a"))
    with pytest.raises(ValueError, match="duplicate relation names"):
        g.validate()


def test_malformed_dump_rejection(tmp_path):
    g = synthetic.make_acm(scale=0.03, seed=0)
    # no meta.json
    with pytest.raises(ValueError, match="no meta.json"):
        datasets.load_hetgraph(tmp_path)
    root = datasets.save_hetgraph(g, tmp_path / "d", name="acm")
    # bad format version
    meta = json.loads((root / "meta.json").read_text())
    meta["format_version"] = 99
    (root / "meta.json").write_text(json.dumps(meta))
    with pytest.raises(ValueError, match="format_version"):
        datasets.load_hetgraph(root)
    meta["format_version"] = datasets.FORMAT_VERSION
    (root / "meta.json").write_text(json.dumps(meta))
    datasets.load_hetgraph(root)  # back to valid
    # out-of-range edge ids on disk -> validate() fires at load time
    with np.load(root / "edges.npz") as z:
        arrs = {k: z[k].copy() for k in z.files}
    rel = g.relations[0][1]
    arrs[f"{rel}__src"][0] = 10 ** 9
    np.savez(root / "edges.npz", **arrs)
    with pytest.raises(ValueError, match="out of range"):
        datasets.load_hetgraph(root)
    arrs[f"{rel}__src"][0] = 0
    np.savez(root / "edges.npz", **arrs)
    # missing relation arrays
    bad = {k: v for k, v in arrs.items() if not k.startswith(f"{rel}__")}
    np.savez(root / "edges.npz", **bad)
    with pytest.raises(ValueError, match="missing edge arrays"):
        datasets.load_hetgraph(root)
    np.savez(root / "edges.npz", **arrs)
    # feature row-count mismatch
    with np.load(root / "features.npz") as z:
        feats = {k: z[k].copy() for k in z.files}
    t0 = g.node_types[0]
    feats[t0] = feats[t0][:-1]
    np.savez(root / "features.npz", **feats)
    with pytest.raises(ValueError, match="features"):
        datasets.load_hetgraph(root)
