"""Golden parity tests for the vectorized, degree-bucketed Semantic Graph
Build against the seed's loop-based implementation.

``_pad_csc_ref`` / ``_compose_ref`` below are verbatim copies of the seed's
per-vertex/per-B loop builds — the golden oracles. The vectorized build must
reproduce them edge-for-edge whenever no random overflow down-sampling is
involved, and match them in the set sense when it is. The bucketed layout
must be a pure re-layout: identical edges, identical logits on every model.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hetgraph, pipeline
from repro.core.flows import FlowConfig
from repro.data import synthetic


# --------------------------------------------------------------------------
# seed (loop-based) golden references. The seed _pad_csc loop lives in
# benchmarks/sgb_build.py (it doubles as the speedup-row baseline there);
# a single shared copy keeps the parity oracle and the benchmark baseline
# from drifting apart.
# --------------------------------------------------------------------------

from benchmarks.sgb_build import _pad_csc_loop as _pad_csc_ref  # noqa: E402


def _compose_ref(ab, bc, cap_fanout, rng):
    """Seed ``_compose``: per-B Python loop (the golden oracle)."""
    a, b1 = ab
    b2, c = bc
    o1 = np.argsort(b1, kind="stable")
    a, b1 = a[o1], b1[o1]
    o2 = np.argsort(b2, kind="stable")
    b2, c = b2[o2], c[o2]
    n_b = int(max(b1.max(initial=-1), b2.max(initial=-1))) + 1
    c1 = np.bincount(b1, minlength=n_b)
    c2 = np.bincount(b2, minlength=n_b)
    s1 = np.concatenate([[0], np.cumsum(c1)[:-1]])
    s2 = np.concatenate([[0], np.cumsum(c2)[:-1]])
    outs_a, outs_c = [], []
    for b in range(n_b):
        if c1[b] == 0 or c2[b] == 0:
            continue
        left = a[s1[b]: s1[b] + c1[b]]
        right = c[s2[b]: s2[b] + c2[b]]
        if len(left) * len(right) > cap_fanout:
            k = cap_fanout
            li = rng.integers(0, len(left), size=k)
            ri = rng.integers(0, len(right), size=k)
            outs_a.append(left[li])
            outs_c.append(right[ri])
        else:
            outs_a.append(np.repeat(left, len(right)))
            outs_c.append(np.tile(right, len(left)))
    if not outs_a:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    return np.concatenate(outs_a), np.concatenate(outs_c)


def _random_edges(rng, num_targets, num_src, num_edges, num_etypes=1):
    src = rng.integers(0, num_src, size=num_edges).astype(np.int64)
    dst = rng.integers(0, num_targets, size=num_edges).astype(np.int64)
    ety = rng.integers(0, num_etypes, size=num_edges).astype(np.int64)
    return src, dst, ety


# --------------------------------------------------------------------------
# _pad_csc golden parity
# --------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(12))
def test_pad_csc_matches_ref_edge_for_edge(seed):
    """No overflow (max_degree=None): bit-identical to the seed loop build,
    including slot order (the pruner's tie-breaking depends on it)."""
    rng = np.random.default_rng(seed)
    t = int(rng.integers(1, 60))
    e = int(rng.integers(0, 500))
    src, dst, ety = _random_edges(rng, t, 100, e, num_etypes=4)
    got = hetgraph._pad_csc(src, dst, t, None, np.random.default_rng(seed), ety)
    want = _pad_csc_ref(src, dst, t, None, np.random.default_rng(seed), ety)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("seed", range(6))
def test_pad_csc_overflow_semantics(seed):
    """With a degree cap: per-row counts equal min(deg, cap), kept neighbors
    are a subset of the true multiset, rows under the cap keep their exact
    arrival order (matching the ref)."""
    rng = np.random.default_rng(seed)
    t, e, cap = 24, 600, 8
    src, dst, ety = _random_edges(rng, t, 50, e)
    nbr, msk, _ = hetgraph._pad_csc(src, dst, t, cap, np.random.default_rng(seed))
    counts = np.bincount(dst, minlength=t)
    np.testing.assert_array_equal(msk.sum(1), np.minimum(counts, cap))
    order = np.argsort(dst, kind="stable")
    ss, dd = src[order], dst[order]
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    ref_nbr, ref_msk, _ = _pad_csc_ref(src, dst, t, cap, np.random.default_rng(seed))
    for v in range(t):
        row_true = ss[starts[v]: starts[v] + counts[v]]
        kept = nbr[v][msk[v]]
        # multiset-subset of the true in-neighbors
        tc = np.bincount(row_true, minlength=50)
        kc = np.bincount(kept, minlength=50)
        assert (kc <= tc).all()
        if counts[v] <= cap:  # intact rows: exact arrival order, as in ref
            np.testing.assert_array_equal(kept, row_true)
            np.testing.assert_array_equal(kept, ref_nbr[v][ref_msk[v]])


def test_pad_csc_empty_and_degenerate():
    empty = np.zeros(0, np.int64)
    nbr, msk, ety = hetgraph._pad_csc(empty, empty, 5, None, np.random.default_rng(0))
    assert nbr.shape == (5, 1) and not msk.any()
    # single edge
    nbr, msk, _ = hetgraph._pad_csc(
        np.array([7]), np.array([2]), 4, None, np.random.default_rng(0)
    )
    assert nbr[2, 0] == 7 and msk.sum() == 1


# --------------------------------------------------------------------------
# _compose golden parity
# --------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(10))
def test_compose_matches_ref_edge_for_edge(seed):
    """No fan-out capping: bit-identical join output (same pair order)."""
    rng = np.random.default_rng(seed)
    e1, e2 = int(rng.integers(0, 300)), int(rng.integers(0, 300))
    ab = (rng.integers(0, 60, e1).astype(np.int64), rng.integers(0, 30, e1).astype(np.int64))
    bc = (rng.integers(0, 30, e2).astype(np.int64), rng.integers(0, 50, e2).astype(np.int64))
    got = hetgraph._compose(ab, bc, 10**9, np.random.default_rng(seed))
    want = _compose_ref(ab, bc, 10**9, np.random.default_rng(seed))
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])


def test_compose_fanout_cap():
    """Capped blocks emit exactly cap_fanout pairs drawn from the block."""
    b = np.zeros(40, np.int64)
    ab = (np.arange(40, dtype=np.int64), b)
    bc = (b, np.arange(40, dtype=np.int64) + 100)
    a, c = hetgraph._compose(ab, bc, 100, np.random.default_rng(0))
    assert len(a) == len(c) == 100
    assert set(a.tolist()) <= set(range(40))
    assert set(c.tolist()) <= set(range(100, 140))


# --------------------------------------------------------------------------
# bucketed layout: pure re-layout of the flat build
# --------------------------------------------------------------------------

def _flat_and_bucketed(builder, *args, **kw):
    flat = builder(*args, **kw, bucket_sizes=None)
    buck = builder(*args, **kw, bucket_sizes=(8, 32, 128))
    if isinstance(flat, dict):
        return list(flat.values()), list(buck.values())
    return flat, buck


@pytest.mark.parametrize("dataset", ["acm", "imdb"])
def test_bucketed_build_is_pure_relayout(dataset):
    g = synthetic.DATASETS[dataset](scale=0.05, seed=0)
    mps = synthetic.METAPATHS[dataset]
    for builder, args in [
        (hetgraph.build_metapath_graphs, (g, mps)),
        (hetgraph.build_relation_graphs, (g,)),
        (hetgraph.build_union_graph, (g,)),
    ]:
        flats, bucks = _flat_and_bucketed(builder, *args, max_degree=64, seed=0)
        for sf, sb in zip(flats, bucks):
            assert isinstance(sb, hetgraph.BucketedSemanticGraph)
            # partition: every target in exactly one bucket
            all_t = np.concatenate([b.targets for b in sb.buckets])
            assert len(all_t) == sf.num_targets
            assert len(np.unique(all_t)) == sf.num_targets
            # tightest-bucket routing
            deg = sf.degrees()
            caps = sb.bucket_capacities
            for b in sb.buckets:
                d = deg[b.targets]
                assert (d <= b.capacity).all()
                tighter = [c for c in caps if c < b.capacity]
                if tighter:
                    assert (d > max(tighter)).all()
            # flat reconstruction is edge-for-edge identical
            np.testing.assert_array_equal(sf.nbr_idx, sb.nbr_idx)
            np.testing.assert_array_equal(sf.nbr_mask, sb.nbr_mask)
            np.testing.assert_array_equal(sf.edge_type, sb.edge_type)
            np.testing.assert_array_equal(sf.degrees(), sb.degrees())
            assert sf.num_edges == sb.num_edges
            # and the bucketed layout never pays more padded slots
            assert sb.padded_slots() <= sf.padded_slots()


# --------------------------------------------------------------------------
# bucket-capacity autotuner
# --------------------------------------------------------------------------

def test_autotune_never_worse_than_static():
    """DP over observed degrees beats (or ties) any static capacity list of
    the same bucket budget — asserted against {8, 32, 128, D_max} on every
    semantic graph of every builder."""
    g = synthetic.DATASETS["imdb"](scale=0.1, seed=0)
    mps = synthetic.METAPATHS["imdb"]
    for builder, args in [
        (hetgraph.build_metapath_graphs, (g, mps)),
        (hetgraph.build_relation_graphs, (g,)),
        (hetgraph.build_union_graph, (g,)),
    ]:
        static = builder(*args, max_degree=256, seed=0,
                         bucket_sizes=hetgraph.DEFAULT_BUCKET_SIZES)
        auto = builder(*args, max_degree=256, seed=0, bucket_sizes="auto")
        if isinstance(static, dict):
            static, auto = list(static.values()), list(auto.values())
        for ss, sa in zip(static, auto):
            assert sa.padded_slots() <= ss.padded_slots(), sa.name
            assert len(sa.buckets) <= 4
            # still a pure relayout: same edges, same degrees
            assert sa.num_edges == ss.num_edges
            np.testing.assert_array_equal(sa.degrees(), ss.degrees())


def test_autotune_degenerate_histograms():
    # uniform degrees: one bucket at exactly that degree
    assert hetgraph.autotune_bucket_sizes(np.full(100, 7)) == (7,)
    # few distinct degrees: one bucket each (zero padded slots)
    caps = hetgraph.autotune_bucket_sizes(np.array([1, 5, 5, 9]), max_buckets=4)
    assert caps == (1, 5, 9)
    # degree-0 targets still need a slot
    assert hetgraph.autotune_bucket_sizes(np.zeros(10)) == (1,)
    # budget binds: never more than max_buckets capacities
    deg = np.arange(1, 200)
    caps = hetgraph.autotune_bucket_sizes(deg, max_buckets=4)
    assert len(caps) <= 4 and caps[-1] == 199
    # a huge launch cost collapses everything into one bucket
    caps = hetgraph.autotune_bucket_sizes(deg, max_buckets=4, launch_cost=1e12)
    assert caps == (199,)


def test_autotune_rounding_objective():
    """round_to makes the DP cost count tile padding: capacities land on
    values whose padded width is no worse than the unrounded optimum's."""
    deg = np.array([3] * 50 + [9] * 50)
    # unrounded: buckets at 3 and 9 (slots 150 + 450)
    assert hetgraph.autotune_bucket_sizes(deg, max_buckets=2) == (3, 9)
    # rounded to 8: both pad to ≤ 16; merging (one cap-9 bucket, pad 16)
    # costs 100×16 = 1600 vs split 50×8 + 50×16 = 1200 → keep the split
    caps = hetgraph.autotune_bucket_sizes(deg, max_buckets=2, round_to=8)
    assert caps == (3, 9)


# --------------------------------------------------------------------------
# grouped ragged-grid layout: pure relayout of the bucket tables
# --------------------------------------------------------------------------

def test_grouped_layout_roundtrip():
    g = synthetic.DATASETS["acm"](scale=0.05, seed=0)
    sgs = hetgraph.build_relation_graphs(
        g, max_degree=48, seed=0, bucket_sizes=(4, 8, 16)
    )
    for sg in sgs:
        lay = sg.grouped()
        # perm inverts the padded grouped rows back to target order
        assert len(np.unique(lay.perm)) == sg.num_targets
        gi = 0
        row_off = 0
        for bi, b in enumerate(sg.buckets):
            t_b, d_b = b.nbr_idx.shape
            rows_p = -(-t_b // lay.t_tile) * lay.t_tile
            cap_p = int(lay.caps_pad[bi])
            n_rt, n_dt = rows_p // lay.t_tile, cap_p // lay.w
            for tiles, table in ((lay.nbr, b.nbr_idx), (lay.msk, b.nbr_mask)):
                rec = (
                    tiles[gi: gi + n_rt * n_dt]
                    .reshape(n_rt, n_dt, lay.t_tile, lay.w)
                    .transpose(0, 2, 1, 3)
                    .reshape(rows_p, cap_p)
                )
                np.testing.assert_array_equal(rec[:t_b, :d_b], table)
            # padding rows/cols carry no valid slots
            rec_m = (
                lay.msk[gi: gi + n_rt * n_dt]
                .reshape(n_rt, n_dt, lay.t_tile, lay.w)
                .transpose(0, 2, 1, 3)
                .reshape(rows_p, cap_p)
            )
            assert not rec_m[t_b:].any() and not rec_m[:, d_b:].any()
            np.testing.assert_array_equal(
                lay.perm[b.targets], row_off + np.arange(t_b)
            )
            np.testing.assert_array_equal(
                lay.row_targets[row_off: row_off + t_b], b.targets
            )
            gi += n_rt * n_dt
            row_off += rows_p
        assert gi == lay.num_steps and row_off == lay.num_rows
        # grid-step metadata is self-consistent
        assert (lay.step_dt < lay.step_ndt).all()
        np.testing.assert_array_equal(
            lay.step_ndt, (lay.caps_pad // lay.w)[lay.step_bucket]
        )


# --------------------------------------------------------------------------
# logits parity: {flat, bucketed, autotuned} × all flows × single vs loop
# dispatch, all three models × two synthetic datasets
# --------------------------------------------------------------------------

MODELS = ["han", "rgat", "simple_hgn"]
DATASETS = ["acm", "imdb"]


@pytest.fixture(scope="module")
def paired_tasks():
    out = {}
    for m in MODELS:
        for d in DATASETS:
            out[(m, d)] = (
                pipeline.prepare(m, d, scale=0.03, max_degree=32, seed=0,
                                 bucket_sizes=None),
                pipeline.prepare(m, d, scale=0.03, max_degree=32, seed=0,
                                 bucket_sizes=(4, 8, 16)),
                pipeline.prepare(m, d, scale=0.03, max_degree=32, seed=0,
                                 bucket_sizes="auto"),
            )
    return out


@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize("dataset", DATASETS)
def test_bucketed_matches_flat_staged(paired_tasks, model, dataset):
    flat, buck, auto = paired_tasks[(model, dataset)]
    a = np.asarray(flat.logits(flat.params, FlowConfig("staged")))
    b = np.asarray(buck.logits(buck.params, FlowConfig("staged")))
    c = np.asarray(auto.logits(auto.params, FlowConfig("staged")))
    np.testing.assert_allclose(a, b, atol=1e-5)
    np.testing.assert_allclose(a, c, atol=1e-5)


@pytest.mark.parametrize("layout", ["bucketed", "autotuned"])
@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize("dataset", DATASETS)
def test_bucketed_flows_agree(paired_tasks, model, dataset, layout):
    """staged_pruned vs fused vs fused_kernel on the bucketed/autotuned
    layouts, each against the flat staged_pruned baseline — the fused_kernel
    rows exercise the single-launch grouped ragged-grid kernel."""
    flat, buck, auto = paired_tasks[(model, dataset)]
    task = buck if layout == "bucketed" else auto
    k = 6
    base = np.asarray(flat.logits(flat.params, FlowConfig("staged_pruned", prune_k=k)))
    staged_b = np.asarray(task.logits(task.params, FlowConfig("staged_pruned", prune_k=k)))
    fused_b = np.asarray(task.logits(task.params, FlowConfig("fused", prune_k=k)))
    kernel_b = np.asarray(task.logits(task.params, FlowConfig("fused_kernel", prune_k=k)))
    np.testing.assert_allclose(base, staged_b, atol=1e-5)
    np.testing.assert_allclose(base, fused_b, atol=1e-5)
    np.testing.assert_allclose(base, kernel_b, atol=1e-5)


@pytest.mark.parametrize("flow", ["staged", "fused", "fused_kernel"])
@pytest.mark.parametrize("model", MODELS)
def test_single_dispatch_matches_bucket_loop(paired_tasks, model, flow):
    """The single-dispatch bucketed NA (one jit region / one grouped kernel
    launch + inverse-permutation gather) reproduces the legacy per-bucket
    loop (slice_targets + out.at[targets].set per bucket) bit-close."""
    _, buck, _ = paired_tasks[(model, "imdb")]
    k = 6
    single = np.asarray(
        buck.logits(buck.params, FlowConfig(flow, prune_k=k))
    )
    loop = np.asarray(
        buck.logits(
            buck.params, FlowConfig(flow, prune_k=k, bucket_dispatch="loop")
        )
    )
    np.testing.assert_allclose(single, loop, atol=1e-5)


def test_bucket_bypass_routing():
    """Buckets with capacity ≤ prune_k take the §4.3 bypass: per-bucket NA
    under the fused flow equals plain staged (unpruned) NA on exactly the
    targets of those buckets — the retention domain is a no-op for them."""
    from repro.core import attention
    from repro.core.flows import run_aggregate_graph

    rng = np.random.default_rng(0)
    t, d, n, h, dh, k = 40, 24, 60, 4, 8, 8
    src = rng.integers(0, n, size=600).astype(np.int64)
    dst = rng.integers(0, t, size=600).astype(np.int64)
    nbr, msk, ety = hetgraph._pad_csc(src, dst, t, d, np.random.default_rng(1))
    sg = hetgraph.bucketize("b", ("x",), "x", nbr, msk, ety, (4, 8, 16))
    low = np.concatenate(
        [b.targets for b in sg.buckets if b.capacity <= k]
    ).astype(np.int64)
    assert low.size > 0, "test graph must have low-degree targets"
    h_proj = jnp.asarray(rng.normal(size=(n, h, dh)), jnp.float32)
    scores = attention.DecomposedScores(
        jnp.asarray(rng.normal(size=(n, h)), jnp.float32),
        jnp.asarray(rng.normal(size=(t, h)), jnp.float32),
    )
    unpruned = np.asarray(
        run_aggregate_graph(FlowConfig("staged"), h_proj, scores, sg)
    )
    fused = np.asarray(
        run_aggregate_graph(FlowConfig("fused", prune_k=k), h_proj, scores, sg)
    )
    # bypass buckets: bit-close to unpruned NA (no retention-domain effect)
    np.testing.assert_allclose(unpruned[low], fused[low], atol=1e-6)
    # and pruning does bite somewhere on the high-degree buckets
    high = np.setdiff1d(np.arange(t), low)
    deg = sg.degrees()
    assert (deg[high] > k).any()
    assert np.abs(unpruned[high] - fused[high]).max() > 1e-4


# --------------------------------------------------------------------------
# shard_layout: the mesh partition of the grouped tile stack is a pure
# re-assignment of whole row blocks (device-free — pure numpy; the
# multi-device execution parity lives in tests/test_sharded.py)
# --------------------------------------------------------------------------

def _sharded_graphs():
    g = synthetic.DATASETS["imdb"](scale=0.08, seed=0)
    return hetgraph.build_relation_graphs(
        g, max_degree=48, seed=0, bucket_sizes=(4, 8, 16)
    )


@pytest.mark.parametrize("n_shards", [1, 2, 3, 4, 8])
def test_shard_layout_partitions_blocks(n_shards):
    """Shards partition the grouped stack's row blocks: every grid step and
    every target lands on exactly one shard, block step-runs move whole and
    keep their in-stack order, and per-shard metadata stays bucket-local."""
    for sg in _sharded_graphs():
        lay = sg.grouped()
        sl = hetgraph.shard_layout(lay, n_shards)
        assert len(sl.shards) == n_shards
        assert sum(s.num_steps for s in sl.shards) == lay.num_steps
        assert sum(s.num_rows for s in sl.shards) == lay.num_rows
        # per-target ownership: global perm covers each target exactly once
        # and agrees with the owning shard's local perm
        owner = sl.perm // sl.num_rows_alloc
        local = sl.perm % sl.num_rows_alloc
        assert owner.min() >= 0 and owner.max() < n_shards
        for s, sh in enumerate(sl.shards):
            mine = np.flatnonzero(owner == s)
            np.testing.assert_array_equal(sh.perm[mine], local[mine])
            others = np.flatnonzero(owner != s)
            assert (sh.perm[others] == -1).all()
            # local rows are unique and inside the shard's real rows (the
            # trailing pad block is never a target's home)
            assert len(np.unique(local[mine])) == mine.size
            assert local.max(initial=-1, where=owner == s) < sh.num_rows
            assert sh.num_rows <= sl.num_rows_alloc - sl.t_tile
            # a shard's tile content is the original block's, verbatim, and
            # rows resolve to the same targets
            if mine.size:
                t0 = mine[0]
                np.testing.assert_array_equal(
                    sh.row_targets[sh.perm[t0]], t0
                )
        # every original step appears on exactly one shard with its tile
        # payload intact: match steps by (bucket, dt, row block's targets)
        seen = np.zeros(lay.num_steps, bool)
        for sh in sl.shards:
            for i in range(sh.num_steps):
                blk_targets = sh.row_targets[
                    sh.step_row[i] * sh.t_tile: (sh.step_row[i] + 1) * sh.t_tile
                ]
                cand = np.flatnonzero(
                    (lay.step_bucket == sh.step_bucket[i])
                    & (lay.step_dt == sh.step_dt[i])
                )
                hits = [
                    g for g in cand
                    if np.array_equal(
                        lay.row_targets[
                            lay.step_row[g] * lay.t_tile:
                            (lay.step_row[g] + 1) * lay.t_tile
                        ],
                        blk_targets,
                    ) and not seen[g]
                ]
                assert hits, "shard step has no unmatched original step"
                gidx = hits[0]
                seen[gidx] = True
                np.testing.assert_array_equal(sh.nbr[i], lay.nbr[gidx])
                np.testing.assert_array_equal(sh.msk[i], lay.msk[gidx])
                np.testing.assert_array_equal(sh.ety[i], lay.ety[gidx])
        assert seen.all()


@pytest.mark.parametrize("n_shards", [2, 4, 8])
def test_shard_layout_balance(n_shards):
    """LPT on per-block D-tile counts: no shard exceeds the mean padded-slot
    load by more than one block's worth of slots (the classic LPT bound for
    any assignment of indivisible blocks)."""
    for sg in _sharded_graphs():
        lay = sg.grouped()
        sl = hetgraph.shard_layout(lay, n_shards)
        slots = sl.padded_slots()
        if lay.num_steps == 0:
            continue
        max_block = int(lay.step_ndt.max()) * lay.t_tile * lay.w
        assert slots.max() - slots.mean() <= max_block
        assert sl.balance() >= 1.0
        # deterministic: same input, same assignment
        sl2 = hetgraph.shard_layout(lay, n_shards)
        np.testing.assert_array_equal(sl.perm, sl2.perm)


def test_shard_layout_degenerate():
    """More shards than row blocks: the extras stay empty but keep valid
    (zero-step) layouts, and every target still resolves."""
    rng = np.random.default_rng(0)
    src = rng.integers(0, 30, size=40).astype(np.int64)
    dst = rng.integers(0, 9, size=40).astype(np.int64)  # T=9 -> 2 blocks max
    nbr, msk, ety = hetgraph._pad_csc(src, dst, 9, 8, np.random.default_rng(1))
    sg = hetgraph.bucketize("tiny", ("x",), "x", nbr, msk, ety, (4,))
    sl = sg.sharded(8)
    assert len(sl.shards) == 8
    nonempty = [s for s in sl.shards if s.num_steps]
    assert 1 <= len(nonempty) <= 8
    owner = sl.perm // sl.num_rows_alloc
    for s in np.unique(owner):
        assert sl.shards[s].num_rows > 0
    # cached: same object back
    assert sg.sharded(8) is sl


@pytest.mark.parametrize("n_shards", [2, 4, 8])
@pytest.mark.parametrize("prune_k", [4, 8, None])
def test_sharded_k2_reads_only_rows_k1_writes(n_shards, prune_k):
    """Every K2 gather step of every shard reads the ids and α of a row
    block that one of the shard's K1 grid steps writes. K1 outputs no step
    writes are uninitialised on a TPU (interpret mode cannot show it), and
    a K2 step that DMAs the feature row of such an id halts the chip."""
    from repro.kernels.fused_prune_aggregate import ops

    for sg in _sharded_graphs():
        sl = sg.sharded(n_shards)
        if sl.num_steps_max == 0:
            continue
        _, (meta, agg, _) = ops._sharded_device(sl, prune_k)
        meta, agg = np.asarray(meta), np.asarray(agg)
        for s in range(n_shards):
            written = np.unique(meta[s, 0])
            read = np.unique(agg[s, 0] // sl.t_tile)
            assert np.isin(read, written).all(), (
                f"{sg.name} shard {s}: K2 reads row blocks "
                f"{np.setdiff1d(read, written)} that no K1 step writes"
            )
