"""Full-scale dataset ingestion + SGB artifact cache + single-dispatch NA.

The paper's 28x claims are measured on ACM/IMDB/DBLP at full scale — the
regime where SGB cost and attention disparity actually bite. This benchmark
runs the whole ingestion path at ``scale=1.0`` for all three datasets:

  * **generate** — the vectorized synthetic generator (the per-target
    edge-loop used to take minutes at full scale; the repeat/cumsum draw
    takes milliseconds — the small-scale ``gen_speedup`` row measures the
    loop baseline where it is still tolerable);
  * **sgb_cold** — the full bucketed SGB build (metapath composition +
    padded-CSC + bucketing + grouped relayout) through the content-
    addressed cache, cache-miss path (build + save);
  * **sgb_cachehit** — the same call again: one npz load + reconstruct.
    Asserted ≥ 10x faster than the cold build at full scale (the whole
    point of paying the build once per dataset instead of once per
    process);
  * **na_fused** — one eager single-dispatch NA stage over the loaded
    semantic graphs (the ``fused`` jnp flow, one jit region per graph).

Rows land in ``BENCH_sgb_scale.json`` via ``benchmarks.common.emit``.
``--smoke`` (CI) runs the same path at small scale with the functional
asserts (miss→hit statuses, layout parity) but not the 10x wall-clock
floor, which only means something when the build is actually expensive.
"""
from __future__ import annotations

import argparse
import shutil
import tempfile
import time
from pathlib import Path

import numpy as np

from benchmarks.common import emit, time_fn
from repro.data import sgb_cache, synthetic

HEADS, DH, PRUNE_K = 4, 8, 8
MAX_DEGREE = 256
SHARDS = 8  # pre-split for the PR 3 mesh path; part of the cached artifact
SPEEDUP_FLOOR = 10.0


def _bipartite_edges_loop(
    rng, n_src, n_dst, mean_deg_dst, comm_src, comm_dst, noise_edges
):
    """The seed implementation: per-target Python loop (golden baseline for
    the vectorized generator — tests/test_datasets.py imports it too)."""
    n_comm = int(comm_src.max()) + 1
    by_comm = [np.where(comm_src == c)[0] for c in range(n_comm)]
    deg = synthetic._power_law_degrees(rng, n_dst, mean_deg_dst)
    srcs, dsts = [], []
    for v in range(n_dst):
        d = deg[v]
        same = rng.random(d) >= noise_edges
        pool_same = by_comm[comm_dst[v]]
        rand_picks = rng.integers(0, n_src, size=d)
        if len(pool_same) > 0:
            same_picks = pool_same[rng.integers(0, len(pool_same), size=d)]
        else:
            same_picks = rand_picks
        picks = np.where(same, same_picks, rand_picks)
        srcs.append(picks)
        dsts.append(np.full(d, v, dtype=np.int64))
    src = np.concatenate(srcs)
    dst = np.concatenate(dsts)
    key = src * n_dst + dst
    _, uniq = np.unique(key, return_index=True)
    return src[uniq].astype(np.int64), dst[uniq].astype(np.int64)


def _time_once(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _na_stage(sgs, total_nodes, rng):
    """One eager fused-flow NA pass over every semantic graph (synthetic
    coefficients — score values don't affect NA cost)."""
    import jax
    import jax.numpy as jnp

    from repro.core.attention import DecomposedScores
    from repro.core.flows import FlowConfig, run_aggregate_graph

    h_proj = jnp.asarray(
        rng.normal(size=(total_nodes, HEADS, DH)), jnp.float32
    )
    theta_src = jnp.asarray(rng.normal(size=(total_nodes, HEADS)), jnp.float32)
    per_sg = []
    for sg in sgs:
        theta_dst = jnp.asarray(
            rng.normal(size=(sg.num_targets, HEADS)), jnp.float32
        )
        per_sg.append((sg, DecomposedScores(theta_src, theta_dst)))
    cfg = FlowConfig("fused", prune_k=PRUNE_K)

    def run():
        return [run_aggregate_graph(cfg, h_proj, sc, sg) for sg, sc in per_sg]

    jax.block_until_ready(run())  # compile outside the timed region
    return run


def bench_gen_speedup(scale: float = 0.25):
    """Loop vs vectorized generator at a scale the loop can still stomach
    (the loop is O(targets) Python iterations; at scale=1.0 it is minutes)."""
    def gen():
        return synthetic.make_dblp(scale=scale, seed=0)

    _, t_vec = _time_once(gen)
    orig = synthetic._bipartite_edges
    synthetic._bipartite_edges = _bipartite_edges_loop
    try:
        _, t_loop = _time_once(gen)
    finally:
        synthetic._bipartite_edges = orig
    emit(
        "sgb_scale_gen_speedup_small", t_vec * 1e6,
        f"scale={scale};loop_us={t_loop * 1e6:.0f}"
        f";speedup_vs_loop={t_loop / t_vec:.1f}x",
    )


def bench_dataset(name: str, scale: float, cache_root: Path, smoke: bool):
    gen = synthetic.DATASETS[name]
    g, t_gen = _time_once(lambda: gen(scale=scale, seed=0))
    n_e = sum(len(s) for s, _ in g.edges.values())
    emit(
        f"sgb_scale_{name}_generate", t_gen * 1e6,
        f"scale={scale};nodes={g.total_nodes};edges={n_e}",
    )

    # full bucketed SGB through the artifact cache: HAN metapath graphs
    # (composition is the expensive stage) + the RGAT relation graphs +
    # the Simple-HGN union graphs, each pre-split 8 ways for the mesh path
    # (shard_layout is part of the production frontend since PR 3) — the
    # complete per-dataset preparation a serving process needs
    mps = synthetic.METAPATHS[name]
    cache_dir = cache_root / name
    kw = dict(
        max_degree=MAX_DEGREE, seed=0,
        bucket_sizes="auto", cache_dir=cache_dir, shards=SHARDS,
    )

    def frontend():
        sgs_mp, st1 = sgb_cache.build_or_load(
            g, "metapath", metapaths=mps, **kw
        )
        sgs_rel, st2 = sgb_cache.build_or_load(g, "relation", **kw)
        union, st3 = sgb_cache.build_or_load(g, "union", **kw)
        return (sgs_mp, sgs_rel, union), (st1, st2, st3)

    # cold: median of 3 full rebuilds (the entry is deleted between reps —
    # the build is deterministic, so this only averages out machine noise)
    cold_ts = []
    for _ in range(3):
        shutil.rmtree(cache_dir, ignore_errors=True)
        (cold_sgs, cold_st), t = _time_once(frontend)
        assert cold_st == ("miss", "miss", "miss"), cold_st
        cold_ts.append(t)
    t_cold = sorted(cold_ts)[1]
    # warm: min of 5 — the load is deterministic work, so the minimum is
    # the noise-free estimate of what a new process pays (fingerprint +
    # key + mmap load); median would fold scheduler noise into the gate
    warm_ts = []
    for _ in range(5):
        (warm_sgs, warm_st), t = _time_once(frontend)
        assert warm_st == ("hit", "hit", "hit"), warm_st
        warm_ts.append(t)
    t_warm = min(warm_ts)
    speedup = t_cold / t_warm
    n_graphs = len(cold_sgs[0]) + len(cold_sgs[1]) + len(cold_sgs[2])
    n_sg_edges = sum(
        sg.num_edges
        for group in (cold_sgs[0], cold_sgs[1], cold_sgs[2].values())
        for sg in group
    )
    emit(
        f"sgb_scale_{name}_sgb_cold", t_cold * 1e6,
        f"graphs={n_graphs};sg_edges={n_sg_edges};status=miss",
    )
    emit(
        f"sgb_scale_{name}_sgb_cachehit", t_warm * 1e6,
        f"speedup_vs_cold={speedup:.1f}x;status=hit",
    )
    # cache-hit layouts are the build's, verbatim — all three stacks,
    # including the union dict (key order and content)
    assert list(cold_sgs[2]) == list(warm_sgs[2])
    pairs = list(zip(
        cold_sgs[0] + cold_sgs[1] + list(cold_sgs[2].values()),
        warm_sgs[0] + warm_sgs[1] + list(warm_sgs[2].values()),
    ))
    assert len(pairs) == n_graphs
    for a, b in pairs:
        assert a.name == b.name
        assert a.num_edges == b.num_edges and a.num_targets == b.num_targets
        np.testing.assert_array_equal(a.target_perm(), b.target_perm())
        np.testing.assert_array_equal(a.nbr_idx, b.nbr_idx)
        shape = sgb_cache._tile_shape(a)
        la, lb = a.grouped(*shape), b.grouped(*shape)
        np.testing.assert_array_equal(la.nbr, lb.nbr)
        np.testing.assert_array_equal(la.perm, lb.perm)
    if not smoke:
        assert speedup >= SPEEDUP_FLOOR, (
            f"{name}: SGB cache hit only {speedup:.1f}x faster than the "
            f"cold full-scale build (need ≥ {SPEEDUP_FLOOR}x)"
        )

    # single-dispatch NA over the cache-loaded metapath graphs
    rng = np.random.default_rng(0)
    run = _na_stage(warm_sgs[0], g.total_nodes, rng)
    t_na = time_fn(run, warmup=1, iters=1 if smoke else 3)
    emit(
        f"sgb_scale_{name}_na_fused", t_na * 1e6,
        f"graphs={len(warm_sgs[0])};flow=fused;prune_k={PRUNE_K}",
    )


def main(smoke: bool = False, keep_cache: str | None = None):
    scale = 0.05 if smoke else 1.0
    if keep_cache:
        cache_root = Path(keep_cache)
        cache_root.mkdir(parents=True, exist_ok=True)
        tmp = None
    else:
        tmp = tempfile.mkdtemp(prefix="sgb_scale_cache_")
        cache_root = Path(tmp)
    try:
        # resolve the kernel tile rule (a jax import) outside every timed
        # region — cold rows must measure the build, not the import
        sgb_cache._tile_rule()
        bench_gen_speedup(scale=0.1 if smoke else 0.25)
        for name in ("acm", "imdb", "dblp"):
            bench_dataset(name, scale, cache_root, smoke)
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--smoke", action="store_true",
        help="small scale, functional asserts only — the CI ingestion gate",
    )
    ap.add_argument(
        "--keep-cache", default=None,
        help="persist the SGB cache here instead of a throwaway tmpdir",
    )
    args = ap.parse_args()
    main(smoke=args.smoke, keep_cache=args.keep_cache)
