"""Per-call serving overhead — legacy eager ``task.logits`` vs the
AOT-compiled ``InferenceSession`` (``task.compile(flow)``).

The legacy entry point re-pays host overhead on EVERY inference call:
eager per-type projection ops, one Python ``run_aggregate_graph`` entry
per semantic graph (jit-cache lookups + device-table fetches, and an
ambient-mesh resolution before the hoist), eager fusion glue. The session
resolves mesh/layouts once at build, AOT-compiles the whole forward into
ONE executable per (flow, mesh, dtype), and dispatches it directly.

Measured per model × {staged, fused, fused_kernel} (rows committed to
``BENCH_session.json`` for the per-PR trajectory):
  * per-call wall time, eager legacy vs session, on the repeated-inference
    serving pattern;
  * the session's parity gap vs the legacy path;
  * Python dispatch accounting across N session calls.

Asserted invariants (CI runs ``--smoke``):
  * session logits are BIT-IDENTICAL to the jitted legacy program (same
    trace, compiled ahead of time) for every model × flow, and within
    5e-5 of the eager legacy dispatch (eager op-by-op execution may round
    the last ULP differently than the fused XLA program — observed only
    on rgat, ≤ 1 ULP);
  * ≥ 2x lower per-call time than the eager legacy path on the jnp flows
    (staged / fused — the CPU production paths; ``fused_kernel`` wall time
    is interpret-mode emulation, dominated by the emulated kernel body, so
    it is reported but not compared — the na_dispatch precedent). The
    assert is carried by dispatch-dominated forwards (≥ 4 NA dispatches:
    rgat 3·R, simple_hgn 2·T — measured 4-9x); han's 2-dispatch forward
    sits near the threshold and is reported without asserting, again the
    na_dispatch precedent (its ≥ 2x is asserted only on ≥ 4-bucket
    layouts);
  * repeated session calls do ZERO Python NA dispatch: no
    ``run_aggregate_graph`` entries, no ``graph_mesh`` lookups
    (``flows.DISPATCH["mesh_lookups"]``), no retraces — while each eager
    legacy call pays one mesh lookup (fused_kernel) and one Python
    dispatch per semantic graph;
  * with ≥ 8 devices (the CI multidevice job; ``--sharded`` asserts it is
    exercised): the 8-way mesh-sharded session is bit-identical to the
    jitted single-device legacy program, still with zero per-call Python
    dispatch.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src:. python benchmarks/session_overhead.py
"""
from __future__ import annotations

import argparse
import functools
import warnings

import jax
import numpy as np

from benchmarks.common import emit as _emit_to, time_fn

# rows land in BENCH_session.json (the serving-trajectory file), not the
# module-stem default; a BENCH_JSON env override still wins
emit = functools.partial(_emit_to, path="BENCH_session.json")
from repro.core import flows, pipeline
from repro.core.flows import FlowConfig
from repro.kernels.fused_prune_aggregate import kernel as fpa_kernel

BUCKETS = (4, 8, 16, 32)
PRUNE_K = 8
CALLS = 5  # repeated-inference window for the dispatch accounting

FLOW_CFGS = [
    ("staged", FlowConfig("staged"), True),
    ("fused", FlowConfig("fused", prune_k=PRUNE_K), True),
    ("fused_kernel", FlowConfig("fused_kernel", prune_k=PRUNE_K), False),
]


def _reset_counters():
    flows.DISPATCH.update(
        graph_calls=0, bucket_calls=0, traces=0, sharded_calls=0,
        mesh_lookups=0,
    )
    fpa_kernel.DISPATCH.update(pallas_calls=0, grouped_traces=0)


def _legacy(task, params, cfg):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return task.logits(params, cfg)


def bench_model(model: str, scale: float, assert_speedup: bool):
    task = pipeline.prepare(
        model, "imdb", scale=scale, max_degree=64, seed=0, bucket_sizes=BUCKETS
    )
    params = task.params
    n_dispatch = len(task.sgs) * task.model.num_layers

    for flow_name, cfg, compare_wall in FLOW_CFGS:
        sess = task.compile(cfg)
        jitted = jax.jit(lambda p: task.model.apply(p, task.batch, cfg))

        # parity: the session IS the legacy program, compiled ahead of time
        ref_jit = np.asarray(jitted(params))
        out = np.asarray(sess(params))
        assert np.array_equal(out, ref_jit), (
            f"{model}/{flow_name}: session logits are not bit-identical to "
            f"the jitted legacy path"
        )
        ref_eager = np.asarray(_legacy(task, params, cfg))
        gap = float(np.abs(out - ref_eager).max())
        np.testing.assert_allclose(out, ref_eager, atol=5e-5)

        # dispatch accounting over a repeated-inference window
        _reset_counters()
        for _ in range(CALLS):
            jax.block_until_ready(sess(params))
        assert flows.DISPATCH["graph_calls"] == 0, flows.DISPATCH
        assert flows.DISPATCH["mesh_lookups"] == 0, flows.DISPATCH
        assert flows.DISPATCH["traces"] == 0
        assert fpa_kernel.DISPATCH["grouped_traces"] == 0
        _reset_counters()
        jax.block_until_ready(_legacy(task, params, cfg))
        legacy_lookups = flows.DISPATCH["mesh_lookups"]
        legacy_dispatch = flows.DISPATCH["graph_calls"]
        assert legacy_dispatch == n_dispatch
        if flow_name == "fused_kernel":
            # the hoist contract: ONE ambient-mesh resolution per eager
            # forward (not one per semantic graph); sessions pay zero
            assert legacy_lookups == 1, legacy_lookups

        t_legacy = time_fn(lambda: _legacy(task, params, cfg), iters=5, warmup=2)
        t_sess = time_fn(lambda: sess(params), iters=5, warmup=2)
        speedup = t_legacy / t_sess
        emit(
            f"session_{model}_{flow_name}_legacy_eager", t_legacy * 1e6,
            f"na_dispatches_per_call={legacy_dispatch}"
            f";mesh_lookups_per_call={legacy_lookups}",
        )
        emit(
            f"session_{model}_{flow_name}_session", t_sess * 1e6,
            f"speedup_vs_eager={speedup:.2f}x;parity_maxdiff={gap:.1e}"
            f";python_dispatch_per_call=0;mesh_lookups_per_call=0",
        )
        if compare_wall and assert_speedup and n_dispatch >= 4:
            assert speedup >= 2.0, (
                f"{model}/{flow_name}: session only {speedup:.2f}x over the "
                f"eager legacy path (need ≥ 2x)"
            )


def bench_sharded(model: str, scale: float):
    """8-way mesh-sharded session vs the single-device legacy program."""
    cfg = FlowConfig("fused_kernel", prune_k=PRUNE_K)
    task = pipeline.prepare(
        model, "imdb", scale=scale, max_degree=64, seed=0, bucket_sizes=BUCKETS
    )
    params = task.params
    ref = np.asarray(
        jax.jit(lambda p: task.model.apply(p, task.batch, cfg))(params)
    )
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:8]), ("data",))
    with jax.set_mesh(mesh):
        sess = task.compile(cfg)
        assert sess.mesh_info is not None and sess.mesh_info[2] == 8, (
            "session did not bind the ambient 8-way mesh"
        )
        out = np.asarray(sess(params))
        assert np.array_equal(out, ref), (
            f"{model}: 8-way sharded session is not bit-identical to the "
            f"single-device legacy program"
        )
        _reset_counters()
        for _ in range(CALLS):
            jax.block_until_ready(sess(params))
        assert flows.DISPATCH["graph_calls"] == 0
        assert flows.DISPATCH["mesh_lookups"] == 0
        assert flows.DISPATCH["sharded_calls"] == 0
        t_sess = time_fn(lambda: sess(params), iters=3, warmup=1)
    emit(
        f"session_sharded_8way_{model}", t_sess * 1e6,
        "parity=bit_identical;python_dispatch_per_call=0"
        ";mesh_lookups_per_call=0",
    )


def main(smoke: bool = False, sharded: bool = False):
    models = ["rgat"] if smoke else ["han", "rgat", "simple_hgn"]
    scale = 0.06
    for model in models:
        bench_model(model, scale, assert_speedup=True)
    if len(jax.devices()) >= 8:
        for model in models if not smoke else ["rgat"]:
            bench_sharded(model, scale)
    elif sharded:
        raise SystemExit(
            "--sharded needs >= 8 devices "
            "(XLA_FLAGS=--xla_force_host_platform_device_count=8)"
        )
    else:
        print("(single-device runtime: sharded-session rows skipped)")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--smoke", action="store_true",
        help="one model, all asserts — the CI serving regression gate",
    )
    ap.add_argument(
        "--sharded", action="store_true",
        help="fail instead of skipping when < 8 devices are available "
        "(the CI multidevice job sets this)",
    )
    main(**vars(ap.parse_args()))
