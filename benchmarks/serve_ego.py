"""Ego-subgraph serving — ``session.query_ego`` vs the full-graph forward.

The ego path's value proposition is locality: a query block's forward
runs on the extracted L-hop neighborhood of its targets, so per-query
work (host rows gathered, bytes read, compiled FLOPs) scales with the
NEIGHBORHOOD, not with ``|V|``. This benchmark proves both halves of
that claim and commits the trajectory to ``BENCH_ego.json``.

Asserted invariants (CI runs ``--smoke``):
  * PARITY: for all 3 models, every ego-batched query's logits match the
    full-graph forward slice within 1e-5 (the ego program is a different
    XLA fusion over the same math — bit-exactness is not expected, 1e-5
    is; HAN exercises the injected-β ``ego_globals`` path);
  * dispatch accounting: every query is served by exactly one
    ``ego_calls`` dispatch or one counted ``ego_fallback`` full forward,
    and the §4.3 pruner bypass fires whenever an ego batch's neighbor
    widths fit under K;
  * SCALING: growing the graph several-fold leaves feature+adjacency
    rows gathered per query nearly flat — the ``ego_scaling`` row
    carries ``rows_per_query`` / ``graph_nodes`` metrics (and
    deliberately NO ``us_per_call``: it exercises ``check_emitted``'s
    generalized any-numeric-metric contract);
  * with >= 8 devices (``--sharded``): ego queries against an 8-way
    mesh-sharded session (its full forward is sharded; ego forwards run
    replicated) still match within 1e-5.

    PYTHONPATH=src:. python benchmarks/serve_ego.py --smoke
"""

from __future__ import annotations

import argparse
import functools
import time
import warnings

import jax
import numpy as np

from benchmarks.common import emit as _emit_to

emit = functools.partial(_emit_to, path="BENCH_ego.json")

from repro.core import flows, pipeline
from repro.core.flows import FlowConfig

PRUNE_K = 8
PARITY_TOL = 1e-5


def _reset_counters():
    for k in flows.DISPATCH:
        flows.DISPATCH[k] = 0


def _queries(rng, num_targets, n, sizes=(1, 4)):
    out = []
    for i in range(n):
        k = min(sizes[i % len(sizes)], num_targets)
        out.append(rng.integers(0, num_targets, size=k).astype(np.int32))
    return out


def bench_model(model: str, scale: float, n_queries: int):
    """Parity + dispatch accounting for one model's ego path."""
    cfg = FlowConfig("fused", prune_k=PRUNE_K)
    task = pipeline.prepare(model, "imdb", scale=scale, max_degree=64, seed=0)
    sess = task.compile(cfg)
    sess.enable_ego(seed=0, sample_sizes=(1, 4))
    full = np.asarray(sess(task.params))
    rng = np.random.default_rng(1)
    queries = _queries(rng, task.batch.num_targets, n_queries)
    for idx in queries:  # warm the signature ladder
        sess.query_ego(task.params, idx)
    _reset_counters()
    sess.ego_planner.stats.reset()
    max_err, t0 = 0.0, time.perf_counter()
    for idx in queries:
        out = np.asarray(sess.query_ego(task.params, idx))
        max_err = max(max_err, float(np.abs(out - full[idx]).max()))
    dt = time.perf_counter() - t0
    d = flows.DISPATCH
    if max_err > PARITY_TOL:
        raise AssertionError(f"{model}: ego parity broke: {max_err:.2e}")
    assert d["ego_calls"] + d["ego_fallback"] == n_queries, d
    assert d["ego_traces"] == 0, f"{model}: ego retraced after warmup: {d}"
    assert d["graph_calls"] == 0 and d["mesh_lookups"] == 0, d
    st = sess.ego_planner.stats
    emit(
        f"ego_{model}",
        dt / n_queries * 1e6,
        f"max_err={max_err:.1e};ego={d['ego_calls']};"
        f"bypass={d['ego_bypass']};fallback={d['ego_fallback']};"
        f"rows_per_query={st.rows_per_query:.1f}",
    )


def bench_scaling(scales, n_queries: int):
    """Rows gathered per query must track the neighborhood, not |V|.

    HAN (depth 1) is the clean demonstrator: its closure IS the targets'
    direct metapath neighborhoods. The graph grows several-fold between
    runs; rows/query must grow far slower (degree-capped neighborhoods
    are scale-free here), and stay a small fraction of |V|.
    """
    rows, nodes = [], []
    for scale in scales:
        task = pipeline.prepare("han", "imdb", scale=scale, max_degree=64, seed=0)
        sess = task.compile(FlowConfig("fused", prune_k=PRUNE_K))
        sess.enable_ego(seed=0, sample_sizes=(1, 4))
        rng = np.random.default_rng(2)
        queries = _queries(rng, task.batch.num_targets, n_queries)
        sess.ego_planner.stats.reset()
        for idx in queries:
            assert sess.query_ego(task.params, idx) is not None
        rows.append(sess.ego_planner.stats.rows_per_query)
        nodes.append(task.batch.total_nodes)
    v_ratio = nodes[-1] / nodes[0]
    r_ratio = rows[-1] / rows[0]
    assert v_ratio >= 2.0, f"scaling run did not grow the graph: {nodes}"
    if r_ratio > 0.5 * v_ratio:
        raise AssertionError(
            f"rows/query grew with |V| ({r_ratio:.2f}x vs graph "
            f"{v_ratio:.2f}x) — extraction is not O(neighborhood)"
        )
    if rows[-1] > 0.25 * nodes[-1]:
        raise AssertionError(
            f"rows/query ({rows[-1]:.0f}) is not small vs |V|={nodes[-1]}"
        )
    emit(
        "ego_scaling",
        None,
        f"graph_growth={v_ratio:.2f}x;rows_growth={r_ratio:.2f}x",
        rows_per_query_small=rows[0],
        rows_per_query_large=rows[-1],
        graph_nodes_small=nodes[0],
        graph_nodes_large=nodes[-1],
    )


def bench_sharded(model: str, scale: float, n_queries: int):
    """Ego queries against the 8-way mesh-sharded session.

    The session's full forward is sharded, ego forwards run replicated —
    parity must hold within 1e-5 (the sharded full forward is itself
    bit-identical to single-device, so this bounds the same fusion drift
    as the single-device rows).
    """
    cfg = FlowConfig("fused_kernel", prune_k=PRUNE_K)
    task = pipeline.prepare(model, "imdb", scale=scale, max_degree=64, seed=0)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:8]), ("data",))
    with jax.set_mesh(mesh):
        sess = task.compile(cfg)
        info = sess.mesh_info
        assert info is not None and info[2] == 8, "no ambient 8-way mesh"
        sess.enable_ego(seed=0, sample_sizes=(1, 4))
        full = np.asarray(sess(task.params))
        rng = np.random.default_rng(3)
        queries = _queries(rng, task.batch.num_targets, n_queries)
        for idx in queries:  # warm
            sess.query_ego(task.params, idx)
        _reset_counters()
        max_err, t0 = 0.0, time.perf_counter()
        for idx in queries:
            out = np.asarray(sess.query_ego(task.params, idx))
            max_err = max(max_err, float(np.abs(out - full[idx]).max()))
        dt = time.perf_counter() - t0
    d = flows.DISPATCH
    if max_err > PARITY_TOL:
        raise AssertionError(f"{model}: sharded ego parity: {max_err:.2e}")
    assert d["ego_calls"] + d["ego_fallback"] == n_queries, d
    emit(
        f"ego_sharded_8way_{model}",
        dt / n_queries * 1e6,
        f"max_err={max_err:.1e};ego={d['ego_calls']};"
        f"fallback={d['ego_fallback']}",
    )


def main(smoke: bool = False, sharded: bool = False):
    if sharded and len(jax.devices()) < 8:
        raise SystemExit(
            "--sharded needs >= 8 devices "
            "(XLA_FLAGS=--xla_force_host_platform_device_count=8)"
        )
    n = 8 if smoke else 24
    if sharded:
        bench_sharded("rgat", 0.05, n)
        return
    for model in ("han", "rgat", "simple_hgn"):
        bench_model(model, 0.06, n)
    bench_scaling((0.05, 0.2) if smoke else (0.05, 0.3), n)


if __name__ == "__main__":
    warnings.filterwarnings("ignore", category=UserWarning)
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--sharded", action="store_true")
    args = ap.parse_args()
    main(smoke=args.smoke, sharded=args.sharded)
