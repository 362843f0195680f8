"""Mesh-sharded grouped NA: multi-device execution parity.

These tests need a multi-device jax runtime; CI's ``multidevice`` job
provides one on CPU via ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(the flag must be set before jax initializes — hence an env var on the job,
not an in-test mutation). On a single-device runtime the whole module
skips; the device-free shard_layout invariants stay covered by
``tests/test_sgb.py``.

The load-bearing claim is BIT-EXACT parity: sharding moves whole row
blocks, every target's retention-domain arithmetic runs on one shard with
the same tile content in the same order as the single-device launch, and
the final all-gather + inverse-permutation gather are exact — so logits
must match bit for bit, not approximately.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import attention, flows, hetgraph, pipeline
from repro.core.flows import FlowConfig, run_aggregate_graph
from repro.kernels.fused_prune_aggregate import kernel as fpa_kernel
from repro.kernels.fused_prune_aggregate import ops as fpa_ops

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs >= 8 devices (XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)

WAYS = (1, 2, 4, 8)
KERNEL = FlowConfig("fused_kernel", prune_k=8)


def _mesh(n):
    return jax.set_mesh(jax.sharding.Mesh(np.array(jax.devices()[:n]), ("data",)))


def _reset():
    flows.DISPATCH.update(
        graph_calls=0, bucket_calls=0, traces=0, sharded_calls=0,
        mesh_lookups=0,
    )
    fpa_kernel.DISPATCH.update(
        pallas_calls=0, grouped_traces=0, sharded_traces=0
    )


@pytest.fixture(scope="module")
def tasks():
    return {
        m: pipeline.prepare(
            m, "imdb", scale=0.04, max_degree=32, seed=0,
            bucket_sizes=(4, 8, 16),
        )
        for m in ("han", "rgat", "simple_hgn")
    }


@pytest.mark.parametrize("model", ["han", "rgat", "simple_hgn"])
@pytest.mark.parametrize("ways", WAYS)
def test_sharded_logits_bit_exact(tasks, model, ways):
    task = tasks[model]
    ref = np.asarray(task.logits(task.params, KERNEL))
    _reset()
    with _mesh(ways):
        out = np.asarray(task.logits(task.params, KERNEL))
    assert flows.DISPATCH["sharded_calls"] > 0, "mesh did not engage sharding"
    np.testing.assert_array_equal(ref, out)


def _custom_graph(num_targets, num_src, num_edges, max_degree, seed=0):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, num_src, size=num_edges).astype(np.int64)
    dst = rng.integers(0, num_targets, size=num_edges).astype(np.int64)
    nbr, msk, ety = hetgraph._pad_csc(
        src, dst, num_targets, max_degree, np.random.default_rng(seed + 1)
    )
    return hetgraph.bucketize("t", ("x",), "x", nbr, msk, ety, (4, 8, 16))


def _na(sg, n_src, seed=0):
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.normal(size=(n_src, 4, 8)), jnp.float32)
    sc = attention.DecomposedScores(
        jnp.asarray(rng.normal(size=(n_src, 4)), jnp.float32),
        jnp.asarray(rng.normal(size=(sg.num_targets, 4)), jnp.float32),
    )
    return h, sc


@pytest.mark.parametrize("ways", [2, 4, 8])
def test_nondivisible_target_count(ways):
    """T = 37: neither the target count nor its row-block count divides any
    shard count — the pad-block filler steps and unequal per-shard rows
    must still reproduce the single-device bits."""
    sg = _custom_graph(num_targets=37, num_src=50, num_edges=400, max_degree=24)
    assert sg.num_targets % ways != 0
    h, sc = _na(sg, 50)
    ref = np.asarray(run_aggregate_graph(KERNEL, h, sc, sg))
    with _mesh(ways):
        out = np.asarray(run_aggregate_graph(KERNEL, h, sc, sg))
    np.testing.assert_array_equal(ref, out)
    # per-shard rows genuinely differ (this is the ragged case)
    sl = sg.sharded(ways)
    assert len({s.num_rows for s in sl.shards}) > 1 or ways == 2


@pytest.mark.parametrize("ways", [2, 8])
def test_all_bypass_bucket_shards(ways):
    """Every degree ≤ prune_k: every bucket takes the §4.3 pruner bypass, so
    every shard is an all-bypass shard (the kernel's direct-copy branch
    under shard_map). Must stay bit-exact."""
    sg = _custom_graph(num_targets=33, num_src=40, num_edges=80, max_degree=6)
    assert sg.max_degree <= KERNEL.prune_k  # bypass everywhere
    h, sc = _na(sg, 40)
    ref = np.asarray(run_aggregate_graph(KERNEL, h, sc, sg))
    with _mesh(ways):
        out = np.asarray(run_aggregate_graph(KERNEL, h, sc, sg))
    np.testing.assert_array_equal(ref, out)


def test_one_pallas_pair_per_shard_per_graph():
    """The tentpole launch invariant: under a mesh, one semantic graph's NA
    traces exactly ONE pallas_call pair — the SPMD program every shard runs
    — however many shards the mesh has."""
    sg = _custom_graph(num_targets=64, num_src=80, num_edges=800, max_degree=32)
    h, sc = _na(sg, 80)
    with _mesh(8):
        jax.clear_caches()
        _reset()
        jax.block_until_ready(run_aggregate_graph(KERNEL, h, sc, sg))
        assert fpa_kernel.DISPATCH["pallas_calls"] == 2
        assert fpa_kernel.DISPATCH["sharded_traces"] == 1
        assert flows.DISPATCH["sharded_calls"] == 1


def test_no_mesh_no_op():
    """Without a mesh the sharded path must not engage; with shard="off" it
    must not engage even under a mesh — and both give the same bits."""
    sg = _custom_graph(num_targets=40, num_src=50, num_edges=300, max_degree=24)
    h, sc = _na(sg, 50)
    _reset()
    ref = np.asarray(run_aggregate_graph(KERNEL, h, sc, sg))
    assert flows.DISPATCH["sharded_calls"] == 0
    off = FlowConfig("fused_kernel", prune_k=8, shard="off")
    with _mesh(4):
        _reset()
        out = np.asarray(run_aggregate_graph(off, h, sc, sg))
        assert flows.DISPATCH["sharded_calls"] == 0
    np.testing.assert_array_equal(ref, out)


@pytest.mark.parametrize("model", ["han", "rgat", "simple_hgn"])
def test_sharded_session_parity(tasks, model):
    """An InferenceSession compiled under an 8-way mesh bakes the
    shard_map'd NA into its executable and stays bit-identical to the
    single-device legacy program — with ZERO per-call Python dispatch
    (no run_aggregate_graph entries, no graph_mesh walks): the mesh was
    resolved once at session build and pinned through the trace."""
    task = tasks[model]
    cfg = KERNEL
    ref = np.asarray(
        jax.jit(lambda p: task.model.apply(p, task.batch, cfg))(task.params)
    )
    with _mesh(8):
        sess = task.compile(cfg)
        assert sess.mesh_info is not None and sess.mesh_info[2] == 8
        out = np.asarray(sess(task.params))
        _reset()
        out2 = np.asarray(sess(task.params))
        assert flows.DISPATCH["graph_calls"] == 0
        assert flows.DISPATCH["sharded_calls"] == 0
        assert flows.DISPATCH["mesh_lookups"] == 0
    np.testing.assert_array_equal(ref, out)
    np.testing.assert_array_equal(out, out2)


@pytest.mark.parametrize("model", ["han", "rgat"])
def test_sharded_serving_frontend(tasks, model):
    """The microbatching front-end composes with an 8-way sharded
    session: query blocks dispatch the mesh-compiled forward plus an
    on-device gather lowered against its SHARDED output aval, and every
    request's rows stay bit-identical to the single-device full forward —
    with one Python dispatch per block and zero NA dispatch."""
    from repro.serve import (
        BatchPolicy, InlineExecutor, ServeFrontend, SystemClock,
        make_workload, run_workload,
    )

    task = tasks[model]
    ref = np.asarray(
        jax.jit(lambda p: task.model.apply(p, task.batch, KERNEL))(
            task.params
        )
    )
    with _mesh(8):
        sess = task.compile(KERNEL)
        assert sess.mesh_info is not None and sess.mesh_info[2] == 8
        fe = ServeFrontend(
            sess, task.params,
            BatchPolicy(capacities=(1, 4, 8), flush_timeout=1e-3),
            clock=SystemClock(), executor=InlineExecutor(),
        )
        wl = make_workload(
            11, task.batch.num_targets, size_range=(1, 3), seed=3
        )
        _reset()
        flows.DISPATCH["query_calls"] = 0
        futs = run_workload(fe, wl)
        assert flows.DISPATCH["graph_calls"] == 0
        assert flows.DISPATCH["mesh_lookups"] == 0
        assert flows.DISPATCH["query_calls"] == fe.stats.blocks > 0
        for w, f in zip(wl, futs):
            np.testing.assert_array_equal(f.result(0), ref[w.targets])


def test_prepare_presharding_under_mesh():
    """pipeline.prepare under an ambient mesh pre-builds every semantic
    graph's shard split at SGB time, with the SAME tile shape the sharded
    dispatch keys its cache on (the build-time partition contract)."""
    with _mesh(4):
        task = pipeline.prepare(
            "rgat", "imdb", scale=0.04, max_degree=32, seed=0,
            bucket_sizes=(4, 8, 16),
        )
    for sg in task.sgs:
        key = (4, *fpa_ops.tile_shape(sg))
        assert key in sg._sharded  # built eagerly, not lazily
    # and with no mesh, prepare leaves split building to first dispatch
    task2 = pipeline.prepare(
        "rgat", "imdb", scale=0.04, max_degree=32, seed=1,
        bucket_sizes=(4, 8, 16),
    )
    assert all(not sg._sharded for sg in task2.sgs)


def test_sharded_ego_query_parity(tasks):
    """Ego-subgraph queries compose with an 8-way mesh-sharded session:
    the session's full forward is sharded, ego forwards run REPLICATED
    (the ego trace pins the mesh to None — zero mesh lookups while
    serving) — and per-query logits match the sharded full forward
    within 1e-5 (which is itself bit-identical to single-device, so
    this bounds the same cross-program fusion drift as the
    single-device ego tests)."""
    task = tasks["rgat"]
    with _mesh(8):
        sess = task.compile(KERNEL)
        assert sess.mesh_info is not None and sess.mesh_info[2] == 8
        sess.enable_ego(seed=0, sample=8, sample_sizes=(1, 4))
        full = np.asarray(sess(task.params))
        rng = np.random.default_rng(5)
        queries = [
            rng.integers(0, task.batch.num_targets, size=s)
            for s in (1, 2, 4, 4)
        ]
        for idx in queries:  # warm the ego signature ladder
            sess.query_ego(task.params, idx)
        _reset()
        for k in ("ego_calls", "ego_bypass", "ego_fallback", "ego_traces"):
            flows.DISPATCH[k] = 0
        for idx in queries:
            out = np.asarray(sess.query_ego(task.params, idx))
            np.testing.assert_allclose(out, full[idx], rtol=0, atol=1e-5)
        d = flows.DISPATCH
        assert d["ego_calls"] + d["ego_fallback"] == len(queries)
        assert d["ego_traces"] == 0, "ego retraced after warmup"
        assert d["mesh_lookups"] == 0


def test_sharded_ego_under_deltas():
    """8-way compose with ``repro.stream``: a streamed edge batch
    merge-upgrades the sharded stack in place (the merge mirrors the
    session's shard splits), the successor session's full forward is
    bit-identical to a cold sharded build of the delta'd graph — and a
    warm ego closure the delta did NOT touch survives the version swap
    with its carried closure and adopted executable: zero new
    ``ego_traces``."""
    from repro.stream import StreamIngestor
    from repro.stream.merge import _degrees_of

    with _mesh(8):
        task = pipeline.prepare(
            "rgat", "imdb", scale=0.04, max_degree=None, seed=0,
            bucket_sizes=(4, 8, 16),
        )
        sess = task.compile(KERNEL)
        assert sess.mesh_info is not None and sess.mesh_info[2] == 8
        sess.enable_ego(seed=0, sample_sizes=(1, 4))
        ing = StreamIngestor(task, sess)
        rng = np.random.default_rng(7)
        qa = np.arange(2, dtype=np.int32)
        np.asarray(sess.query_ego(task.params, qa))  # warm trace + closure
        full_a, _ = sess.ego_planner._closure(qa.astype(np.int64))

        # an absorbable target OUTSIDE the warm closure: guaranteed
        # absorb tier, guaranteed not to invalidate qa's closure
        g = ing.graph
        s_t, rel, d_t = g.relations[0]
        sg = next(s for s in ing.sgs if s.name == rel)
        bucket_of, row_of = sg.row_lookup()
        avoid = set(full_a.get(d_t, np.zeros(0, np.int64)).tolist())
        cand = np.array(
            [i for i in range(g.num_nodes[d_t]) if i not in avoid],
            dtype=np.int64,
        )
        deg = _degrees_of(sg, cand, bucket_of, row_of)
        caps = np.asarray(sg.bucket_capacities)[bucket_of[cand]]
        tgt = int(cand[deg + 1 <= caps][0])

        traces0 = flows.DISPATCH["ego_traces"]
        rep = ing.ingest({rel: (
            rng.integers(0, g.num_nodes[s_t], 1),
            np.array([tgt], dtype=np.int64),
        )})
        assert rep.stats.absorbed_slices >= 1
        assert not rep.stats.full_rebuild
        assert rep.closures_carried >= 1 and rep.exes_adopted >= 1

        got = np.asarray(ing.session.query_ego(task.params, qa))
        assert flows.DISPATCH["ego_traces"] == traces0, (
            "clean ego closure retraced across the sharded version swap"
        )
        assert ing.session.ego_planner.stats.closure_hits >= 1

        cold = pipeline.prepare(
            "rgat", ing.graph, max_degree=None, seed=0,
            bucket_sizes=(4, 8, 16),
        )
        ref = np.asarray(cold.compile(KERNEL)(task.params))
        np.testing.assert_array_equal(
            np.asarray(ing.session(task.params)), ref
        )
        np.testing.assert_allclose(got, ref[qa], rtol=0, atol=1e-5)
