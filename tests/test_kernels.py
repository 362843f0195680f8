"""Per-kernel validation: shape/dtype sweeps against the pure-jnp oracles
(interpret mode executes the Pallas kernel bodies on CPU)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fused_prune_aggregate.ops import fused_prune_aggregate
from repro.kernels.fused_prune_aggregate.ref import fused_prune_aggregate_ref
from repro.kernels.topk_decode_attention.kernel import topk_decode_attention_pallas
from repro.kernels.topk_decode_attention.ref import topk_decode_attention_ref
from repro.kernels.topk_select.ops import topk_select
from repro.kernels.topk_select.ref import topk_select_ref


@pytest.mark.parametrize(
    "t,d,k", [(3, 17, 4), (8, 128, 50), (13, 300, 7), (1, 1, 1), (5, 260, 64)]
)
def test_topk_select_sweep(t, d, k, rng):
    s = rng.normal(size=(t, d)).astype(np.float32)
    m = rng.random((t, d)) < 0.8
    _, i1 = topk_select(jnp.asarray(s), jnp.asarray(m), k)
    _, i2 = topk_select_ref(jnp.asarray(s), jnp.asarray(m), k)
    for row in range(t):
        a = set(np.asarray(i1[row])[np.asarray(i1[row]) >= 0].tolist())
        b = set(np.asarray(i2[row])[np.asarray(i2[row]) >= 0].tolist())
        assert a == b, f"row {row}: {a} != {b}"


@pytest.mark.parametrize(
    "t,d,k",
    [
        # shapes deliberately NOT multiples of the (8, 128) kernel tile
        (7, 100, 4), (9, 129, 8), (8, 127, 8), (15, 255, 16), (1, 3, 2),
        # exact tile boundary for contrast
        (8, 128, 8), (16, 256, 4),
    ],
)
def test_topk_select_pallas_edge_shapes(t, d, k, rng):
    """Pallas pruner on tile-unaligned shapes: the kernel pads to (8, 128)
    tiles internally; padded slots must never leak into the result."""
    s = rng.normal(size=(t, d)).astype(np.float32)
    m = rng.random((t, d)) < 0.7
    v1, i1 = topk_select(jnp.asarray(s), jnp.asarray(m), k)
    v2, i2 = topk_select_ref(jnp.asarray(s), jnp.asarray(m), k)
    i1, i2 = np.asarray(i1), np.asarray(i2)
    v1 = np.asarray(v1)
    for row in range(t):
        a = i1[row][i1[row] >= 0]
        b = i2[row][i2[row] >= 0]
        assert set(a.tolist()) == set(b.tolist()), row
        # ids must address real slots, never the kernel's padding columns
        assert (a < d).all() and (a >= 0).all()
        # values at kept slots equal the input scores there
        np.testing.assert_array_equal(np.sort(v1[row][: len(a)])[::-1],
                                      np.sort(s[row][a])[::-1])


@pytest.mark.parametrize("t,d", [(3, 40), (8, 128), (9, 130)])
def test_topk_select_pallas_k1(t, d, rng):
    """k=1 degenerate retention domain: the single kept slot is the row
    argmax of the masked scores (earliest slot on ties)."""
    s = rng.normal(size=(t, d)).astype(np.float32)
    m = rng.random((t, d)) < 0.8
    _, ids = topk_select(jnp.asarray(s), jnp.asarray(m), 1)
    ids = np.asarray(ids)[:, 0]
    for row in range(t):
        if m[row].any():
            masked = np.where(m[row], s[row], -np.inf)
            assert ids[row] == int(np.argmax(masked)), row
        else:
            assert ids[row] == -1, row


def test_topk_select_pallas_all_masked_rows(rng):
    """Rows with zero valid neighbors must come back empty (-1 ids), even
    when interleaved with dense rows and on tile-unaligned shapes."""
    t, d, k = 10, 137, 6
    s = rng.normal(size=(t, d)).astype(np.float32)
    m = rng.random((t, d)) < 0.6
    m[1] = False
    m[4] = False
    m[9] = False
    v, ids = topk_select(jnp.asarray(s), jnp.asarray(m), k)
    ids = np.asarray(ids)
    from repro.kernels.common import NEG

    for row in (1, 4, 9):
        assert (ids[row] == -1).all(), row
        assert (np.asarray(v)[row] <= NEG / 2).all(), row
    for row in (0, 2, 3, 5, 6, 7, 8):
        want = min(k, int(m[row].sum()))
        assert (ids[row] >= 0).sum() == want, row


def test_interpret_mode_follows_the_backend(monkeypatch):
    """One backend decision for every kernel: interpret on CPU, Mosaic on
    TPU, and a loud error anywhere else."""
    import jax

    from repro.kernels import common

    for backend, want in (("cpu", True), ("tpu", False)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert common.interpret_mode() is want
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        common.interpret_mode()


@pytest.mark.parametrize(
    "t,d,h,dh,n,k",
    [(11, 70, 8, 8, 200, 5), (8, 128, 8, 8, 64, 50), (5, 33, 4, 16, 40, 33),
     (2, 7, 2, 4, 10, 3)],
)
def test_fused_prune_aggregate_sweep(t, d, h, dh, n, k, rng):
    hp = rng.normal(size=(n, h, dh)).astype(np.float32)
    ts = rng.normal(size=(n, h)).astype(np.float32)
    td = rng.normal(size=(t, h)).astype(np.float32)
    idx = rng.integers(0, n, size=(t, d)).astype(np.int32)
    msk = rng.random((t, d)) < 0.85
    out1 = fused_prune_aggregate(
        jnp.asarray(hp), jnp.asarray(ts), jnp.asarray(td),
        jnp.asarray(idx), jnp.asarray(msk), prune_k=k,
    )
    out2 = fused_prune_aggregate_ref(
        jnp.asarray(ts[idx]), jnp.asarray(msk), jnp.asarray(td),
        jnp.asarray(idx), jnp.asarray(hp), k,
    )
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=2e-5)


def test_fused_prune_aggregate_with_rel_term(rng):
    """Simple-HGN path: per-edge-type term enters the ranking scalar."""
    t, d, h, dh, n, r, k = 6, 40, 4, 8, 50, 5, 8
    hp = rng.normal(size=(n, h, dh)).astype(np.float32)
    ts = rng.normal(size=(n, h)).astype(np.float32)
    td = rng.normal(size=(t, h)).astype(np.float32)
    tr = rng.normal(size=(r, h)).astype(np.float32)
    idx = rng.integers(0, n, size=(t, d)).astype(np.int32)
    ety = rng.integers(0, r, size=(t, d)).astype(np.int32)
    msk = rng.random((t, d)) < 0.9
    out1 = fused_prune_aggregate(
        jnp.asarray(hp), jnp.asarray(ts), jnp.asarray(td),
        jnp.asarray(idx), jnp.asarray(msk),
        theta_rel=jnp.asarray(tr), edge_type=jnp.asarray(ety), prune_k=k,
    )
    out2 = fused_prune_aggregate_ref(
        jnp.asarray(ts[idx] + tr[ety]), jnp.asarray(msk), jnp.asarray(td),
        jnp.asarray(idx), jnp.asarray(hp), k,
    )
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=2e-5)


# --------------------------------------------------------------------------
# grouped ragged-grid kernel: single-launch NA over all degree buckets
# --------------------------------------------------------------------------


def _random_bucketed(rng, t, d, n, caps, num_etypes=1, edges=600):
    from repro.core import hetgraph

    src = rng.integers(0, n, size=edges).astype(np.int64)
    # heavy-tailed destination draw so every degree bucket gets targets
    dst = np.minimum((t * rng.random(edges) ** 3).astype(np.int64), t - 1)
    ety = rng.integers(0, num_etypes, size=edges).astype(np.int64)
    nbr, msk, et = hetgraph._pad_csc(
        src, dst, t, d, np.random.default_rng(7), ety
    )
    return hetgraph.bucketize(
        "g", ("x",), "x", nbr, msk, et, caps, num_edge_types=num_etypes
    )


@pytest.mark.parametrize(
    "caps,k",
    [
        # multi-bucket, pruned + bypass mix
        ((4, 8, 16), 6),
        # tile-unaligned capacities (not multiples of the kernel's W=8)
        ((5, 13), 7),
        # bucket count of 1 (single capacity covers everything)
        ((64,), 6),
        # all-bypass: every capacity ≤ K, the kernel's direct-copy branch
        ((4, 8), 100),
        # no pruning at all (k=None → unpruned NA through the grouped grid)
        ((4, 8, 16), None),
    ],
)
def test_grouped_matches_ref_and_per_bucket_path(caps, k, rng):
    """Golden parity: the single-launch grouped kernel vs (a) the per-bucket
    oracle and (b) the legacy per-bucket dispatch path."""
    from repro.core import attention
    from repro.core.flows import FlowConfig, run_aggregate_graph
    from repro.kernels.fused_prune_aggregate.ops import (
        fused_prune_aggregate_grouped,
    )
    from repro.kernels.fused_prune_aggregate.ref import (
        fused_prune_aggregate_grouped_ref,
    )

    t, d, n, h, dh = 30, 40, 50, 4, 8
    sg = _random_bucketed(rng, t, d, n, caps)
    hp = jnp.asarray(rng.normal(size=(n, h, dh)), jnp.float32)
    ts = jnp.asarray(rng.normal(size=(n, h)), jnp.float32)
    td = jnp.asarray(rng.normal(size=(t, h)), jnp.float32)
    out_k = fused_prune_aggregate_grouped(hp, ts, td, sg, prune_k=k)
    out_r = fused_prune_aggregate_grouped_ref(hp, ts, td, sg, prune_k=k)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=2e-5)
    scores = attention.DecomposedScores(ts, td)
    out_loop = run_aggregate_graph(
        FlowConfig("fused_kernel", prune_k=k, bucket_dispatch="loop"),
        hp, scores, sg,
    )
    np.testing.assert_allclose(
        np.asarray(out_k), np.asarray(out_loop), atol=2e-5
    )


def test_grouped_with_rel_term(rng):
    """Simple-HGN path through the grouped grid: the per-edge-type term
    enters the ranking scalar of every bucket."""
    from repro.kernels.fused_prune_aggregate.ops import (
        fused_prune_aggregate_grouped,
    )
    from repro.kernels.fused_prune_aggregate.ref import (
        fused_prune_aggregate_grouped_ref,
    )

    t, d, n, h, dh, r = 24, 32, 40, 4, 8, 5
    sg = _random_bucketed(rng, t, d, n, (4, 12), num_etypes=r)
    hp = jnp.asarray(rng.normal(size=(n, h, dh)), jnp.float32)
    ts = jnp.asarray(rng.normal(size=(n, h)), jnp.float32)
    td = jnp.asarray(rng.normal(size=(t, h)), jnp.float32)
    tr = jnp.asarray(rng.normal(size=(r, h)), jnp.float32)
    out_k = fused_prune_aggregate_grouped(
        hp, ts, td, sg, theta_rel=tr, prune_k=6
    )
    out_r = fused_prune_aggregate_grouped_ref(
        hp, ts, td, sg, theta_rel=tr, prune_k=6
    )
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=2e-5)


def test_grouped_empty_bucket_and_empty_graph(rng):
    """A hand-built graph with an empty bucket in the tuple, and a graph
    with zero edges: both must survive the grouped launch."""
    from repro.core import hetgraph
    from repro.kernels.fused_prune_aggregate.ops import (
        fused_prune_aggregate_grouped,
    )
    from repro.kernels.fused_prune_aggregate.ref import (
        fused_prune_aggregate_grouped_ref,
    )

    n, h, dh = 30, 4, 8
    hp = jnp.asarray(rng.normal(size=(n, h, dh)), jnp.float32)
    ts = jnp.asarray(rng.normal(size=(n, h)), jnp.float32)

    sg = _random_bucketed(rng, 12, 16, n, (4, 8), edges=120)
    empty = hetgraph.DegreeBucket(
        targets=np.zeros(0, np.int32),
        nbr_idx=np.zeros((0, 6), np.int32),
        nbr_mask=np.zeros((0, 6), bool),
        edge_type=np.zeros((0, 6), np.int32),
    )
    sg_e = hetgraph.BucketedSemanticGraph(
        "e", ("x",), "x", sg.num_targets, (empty,) + sg.buckets
    )
    td = jnp.asarray(rng.normal(size=(sg.num_targets, h)), jnp.float32)
    out = fused_prune_aggregate_grouped(hp, ts, td, sg_e, prune_k=5)
    ref = fused_prune_aggregate_grouped_ref(hp, ts, td, sg_e, prune_k=5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    # zero-edge graph: every target degree 0 → all-zero output
    z_nbr = np.zeros((5, 1), np.int32)
    z_msk = np.zeros((5, 1), bool)
    sg_z = hetgraph.bucketize(
        "z", ("x",), "x", z_nbr, z_msk, np.zeros((5, 1), np.int32), (2,)
    )
    td5 = jnp.asarray(rng.normal(size=(5, h)), jnp.float32)
    out_z = fused_prune_aggregate_grouped(hp, ts, td5, sg_z, prune_k=3)
    assert out_z.shape == (5, h, dh)
    np.testing.assert_allclose(np.asarray(out_z), 0.0, atol=0)


def test_grouped_is_one_pallas_pair(rng):
    """The tentpole invariant: however many buckets, one launch traces
    exactly one pallas_call pair."""
    from repro.kernels.fused_prune_aggregate import kernel as fpa_kernel
    from repro.kernels.fused_prune_aggregate.ops import (
        fused_prune_aggregate_grouped,
    )

    t, d, n, h, dh = 40, 48, 60, 4, 8
    sg = _random_bucketed(rng, t, d, n, (4, 8, 16, 32), edges=900)
    assert len(sg.buckets) >= 4
    hp = jnp.asarray(rng.normal(size=(n, h, dh)), jnp.float32)
    ts = jnp.asarray(rng.normal(size=(n, h)), jnp.float32)
    td = jnp.asarray(rng.normal(size=(t, h)), jnp.float32)
    import jax

    jax.clear_caches()
    before = fpa_kernel.DISPATCH["pallas_calls"]
    jax.block_until_ready(fused_prune_aggregate_grouped(hp, ts, td, sg, prune_k=6))
    assert fpa_kernel.DISPATCH["pallas_calls"] - before == 2


@pytest.mark.parametrize(
    "b,h,hkv,dh,s,k",
    [(2, 8, 2, 16, 200, 12), (3, 4, 4, 8, 128, 5), (1, 16, 4, 32, 300, 50)],
)
def test_topk_decode_attention_sweep(b, h, hkv, dh, s, k, rng):
    q = rng.normal(size=(b, h, dh)).astype(np.float32)
    kc = rng.normal(size=(b, s, hkv, dh)).astype(np.float32)
    vc = rng.normal(size=(b, s, hkv, dh)).astype(np.float32)
    lens = rng.integers(k + 1, s, size=(b,)).astype(np.int32)
    o1 = topk_decode_attention_pallas(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc), jnp.asarray(lens), k
    )
    o2 = topk_decode_attention_ref(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc), jnp.asarray(lens), k
    )
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)


def test_topk_decode_attention_k_geq_len_equals_full(rng):
    from repro.kernels.topk_decode_attention.ref import full_decode_attention_ref

    b, h, hkv, dh, s = 2, 4, 2, 8, 64
    q = jnp.asarray(rng.normal(size=(b, h, dh)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(b, s, hkv, dh)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(b, s, hkv, dh)), jnp.float32)
    lens = jnp.asarray([40, 64], jnp.int32)
    o1 = topk_decode_attention_pallas(q, kc, vc, lens, s)
    o2 = full_decode_attention_ref(q, kc, vc, lens)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)
