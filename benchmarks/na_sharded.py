"""Mesh-sharded grouped NA — multi-device scaling of the single-launch path.

PR 2 collapsed bucketed NA to ONE kernel-pair launch per semantic graph;
this benchmark measures the distributed follow-on: the grouped tile stack
partitioned by target row blocks across a ``("data",)`` mesh
(``hetgraph.shard_layout``), one kernel pair PER SHARD under ``shard_map``
with shard-local θ_*v gathers, and a single all-gather + global inverse
permutation restoring target order.

Runs on CPU by forcing host-platform devices (the CI recipe):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src:. python benchmarks/na_sharded.py --smoke

Emitted rows (committed to ``BENCH_na_sharded.json`` for the per-PR
trajectory):
  * 1/2/4/8-way NA-stage wall time (interpret-mode kernels — the numbers
    track dispatch/partition overhead, not TPU compute scaling);
  * per-shard padded-slot balance (max/mean; 1.0 = perfect) — the
    load-balance claim of the row-block partition;
  * launch + trace counts per configuration.

Asserted invariants (CI runs ``--smoke``):
  * sharded NA is bit-identical to the single-device launch at every mesh
    size (whole row blocks move; per-target arithmetic is unchanged);
  * ONE pallas_call pair traced per semantic graph under the mesh — the
    SPMD program each shard runs, i.e. one launch pair per shard;
  * padded-slot balance stays within one row block of perfect (the LPT
    assignment bound).
"""
from __future__ import annotations

# must precede any jax import: device count is fixed at backend init
import os

_FLAG = "--xla_force_host_platform_device_count=8"
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _FLAG).strip()

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_fn
from repro.core import flows, pipeline
from repro.core.attention import DecomposedScores
from repro.core.flows import FlowConfig, run_aggregate_graph
from repro.kernels.fused_prune_aggregate import kernel as fpa_kernel

BUCKETS = (4, 8, 16, 32)
HEADS, DH = 4, 8
PRUNE_K = 8
WAYS = (1, 2, 4, 8)


def _mesh(n):
    return jax.set_mesh(jax.sharding.Mesh(np.array(jax.devices()[:n]), ("data",)))


def _na_stage(task, rng):
    """Synthetic per-graph coefficients (score values don't affect NA cost);
    isolates partition + dispatch + aggregation."""
    n = task.graph.total_nodes
    h_proj = jnp.asarray(rng.normal(size=(n, HEADS, DH)), jnp.float32)
    theta_src = jnp.asarray(rng.normal(size=(n, HEADS)), jnp.float32)
    per_sg = []
    for sg in task.sgs:
        theta_dst = jnp.asarray(
            rng.normal(size=(sg.num_targets, HEADS)), jnp.float32
        )
        theta_rel = None
        if sg.num_edge_types > 1:
            theta_rel = jnp.asarray(
                rng.normal(size=(sg.num_edge_types, HEADS)), jnp.float32
            )
        per_sg.append((sg, DecomposedScores(theta_src, theta_dst, theta_rel)))

    def run(cfg):
        return [run_aggregate_graph(cfg, h_proj, sc, sg) for sg, sc in per_sg]

    return run, per_sg, h_proj


def _reset_counters():
    flows.DISPATCH.update(
        graph_calls=0, bucket_calls=0, traces=0, sharded_calls=0
    )
    fpa_kernel.DISPATCH.update(
        pallas_calls=0, grouped_traces=0, sharded_traces=0
    )


def bench_model(model: str, size: str, scale: float):
    cfg = FlowConfig("fused_kernel", prune_k=PRUNE_K)
    task = pipeline.prepare(
        model, "imdb", scale=scale, max_degree=64, seed=0, bucket_sizes=BUCKETS
    )
    rng = np.random.default_rng(0)
    run, per_sg, h_proj = _na_stage(task, rng)

    # single-device reference: values AND baseline wall time
    refs = [np.asarray(z) for z in run(cfg)]
    t_1dev = time_fn(lambda: run(cfg), iters=1, warmup=1)

    for ways in WAYS:
        with _mesh(ways):
            # launch accounting + bit-exact parity, graph by graph with a
            # cleared cache (trace counting over the whole stage would
            # undercount on jit-cache hits between same-shaped graphs)
            for (sg, sc), ref in zip(per_sg, refs):
                jax.clear_caches()
                _reset_counters()
                out = run_aggregate_graph(cfg, h_proj, sc, sg)
                jax.block_until_ready(out)
                pairs = fpa_kernel.DISPATCH["pallas_calls"] // 2
                assert pairs == 1, (
                    f"{model}/{size}/{sg.name}/{ways}way: sharded NA traced "
                    f"{pairs} pallas pairs for one semantic graph (want 1 — "
                    f"the per-shard SPMD program)"
                )
                assert flows.DISPATCH["sharded_calls"] == 1
                assert np.array_equal(np.asarray(out), ref), (
                    f"{model}/{size}/{sg.name}/{ways}way: sharded NA is not "
                    f"bit-identical to the single-device launch"
                )
            # padded-slot balance of the row-block partition
            balances, slots_all = [], []
            for sg, _ in per_sg:
                sl = sg.sharded(ways)
                balances.append(sl.balance())
                slots_all.append(sl.padded_slots())
                max_block = (
                    int(sg.grouped().step_ndt.max(initial=1))
                    * sl.t_tile * sl.w
                )
                slots = sl.padded_slots()
                assert slots.max() - slots.mean() <= max_block, (
                    f"{model}/{sg.name}/{ways}way: padded-slot imbalance "
                    f"{slots} exceeds one row block ({max_block})"
                )
            balance = max(balances)
            t_ways = time_fn(lambda: run(cfg), iters=1, warmup=1)
            emit(
                f"na_sharded_{size}_{model}_{ways}way", t_ways * 1e6,
                f"vs_1dev={t_1dev / t_ways:.2f}x;balance_maxmean={balance:.3f}"
                f";pallas_pairs_per_graph=1;graphs={len(per_sg)}"
                f";shard_slots={[int(s.sum()) for s in slots_all]}",
            )
    emit(
        f"na_sharded_{size}_{model}_1dev_ref", t_1dev * 1e6,
        f"graphs={len(per_sg)};targets={sum(sg.num_targets for sg, _ in per_sg)}",
    )


def main(smoke: bool = False):
    assert len(jax.devices()) >= max(WAYS), (
        f"need {max(WAYS)} devices, got {len(jax.devices())} — set "
        f"XLA_FLAGS={_FLAG} before jax initializes"
    )
    sizes = [("small", 0.06)]
    if not smoke:
        sizes.append(("medium", 0.2))
    models = ["rgat"] if smoke else ["han", "rgat", "simple_hgn"]
    for size, scale in sizes:
        for model in models:
            bench_model(model, size, scale)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--smoke", action="store_true",
        help="small graph, one model, all asserts — the CI multidevice gate",
    )
    main(**vars(ap.parse_args()))
