"""Microbatched serving throughput — ``repro.serve.ServeFrontend`` vs
the serial one-request-at-a-time loop over the SAME ``InferenceSession``.

The front-end's whole value proposition is amortization: the per-block
cost of ``session.query`` is one full forward regardless of how many
requests share the block, so packing a saturated request stream into
capacity-bucketed query blocks divides the forward count by the mean
batch size while the serial baseline pays one forward PER REQUEST. This
benchmark replays the same seeded ``repro.serve.load`` workload through
both paths and commits the p50/p99/QPS trajectory to ``BENCH_serve.json``.

Measured per model (flow = fused, the CPU production path):
  * serial baseline: per-request wall time, p50/p99 latency, QPS;
  * microbatched front-end (inline-driven, saturation regime): per-request
    wall time, p50/p99 latency, QPS, mean batch, pad fraction;
  * (full run) multi-tenant weight streaming: two param versions through
    one donate_params executable.

Asserted invariants (CI runs ``--smoke``):
  * BIT-EXACT parity: every microbatched result equals both the serial
    result and the full-forward slice ``session(params)[targets]`` —
    query blocks dispatch THE session executable plus an on-device
    gather, so a different answer is impossible by construction;
  * microbatched throughput ≥ 2x serial once blocks saturate (mean batch
    ≥ 8 — guaranteed here by the saturation-regime workload);
  * serving does ZERO Python NA dispatch and zero mesh lookups: exactly
    one ``query_calls`` dispatch per emitted block, no retraces;
  * with ≥ 8 devices (the CI multidevice job; ``--sharded`` asserts it
    is exercised): the front-end over an 8-way mesh-sharded session
    stays bit-identical to the single-device full forward.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src:. python benchmarks/serve_load.py
"""
from __future__ import annotations

import argparse
import functools
import time
import warnings

import jax
import numpy as np

from benchmarks.common import emit as _emit_to

emit = functools.partial(_emit_to, path="BENCH_serve.json")
from repro.core import flows, pipeline
from repro.core.flows import FlowConfig
from repro.serve import (
    BatchPolicy,
    InlineExecutor,
    ServeFrontend,
    SystemClock,
    WeightPlane,
    make_workload,
    run_serial,
    run_workload,
)

PRUNE_K = 8
POLICY = BatchPolicy(capacities=(1, 4, 8, 16), flush_timeout=2e-3)
N_REQUESTS = 64


def _reset_counters():
    flows.DISPATCH.update(
        graph_calls=0, bucket_calls=0, traces=0, sharded_calls=0,
        mesh_lookups=0, query_calls=0,
    )


def _frontend(sess, params):
    """A fresh inline front-end (the deterministic driver: the benchmark
    pumps the drain → dispatch → resolve loop itself, so the measured
    window contains no thread scheduling noise — the same code path the
    threaded executor runs)."""
    return ServeFrontend(
        sess, params, POLICY, clock=SystemClock(), executor=InlineExecutor()
    )


def _stats_derived(stats):
    s = stats.summary()
    return (
        f"p50_ms={s['p50_ms']:.2f};p99_ms={s['p99_ms']:.2f}"
        f";qps={s['qps']:.0f}"
    )


def bench_model(model: str, scale: float, assert_speedup: bool):
    cfg = FlowConfig("fused", prune_k=PRUNE_K)
    task = pipeline.prepare(
        model, "imdb", scale=scale, max_degree=64, seed=0
    )
    params = task.params
    sess = task.compile(cfg)
    full = np.asarray(sess(params))

    # saturation regime: everything arrives at t0, so the collector packs
    # maximal blocks — the regime where microbatching has to pay off
    wl = make_workload(
        N_REQUESTS, task.batch.num_targets, rate=None, size_range=(1, 4),
        seed=0,
    )

    # -- serial baseline (one padded dispatch per request) -----------------
    run_serial(sess, params, wl, POLICY, SystemClock())  # warm
    t0 = time.perf_counter()
    serial_outs, serial_stats = run_serial(
        sess, params, wl, POLICY, SystemClock()
    )
    t_serial = time.perf_counter() - t0

    # -- microbatched front-end --------------------------------------------
    with _frontend(sess, params) as fe:
        run_workload(fe, wl)  # warm (fills every jit/dispatch cache)
    fe = _frontend(sess, params)
    _reset_counters()
    t0 = time.perf_counter()
    futs = run_workload(fe, wl)
    t_micro = time.perf_counter() - t0
    dispatch = dict(flows.DISPATCH)
    stats = fe.stats
    fe.close()

    # bit-exact parity, both ways: microbatched == serial == full forward
    for w, f, s_out in zip(wl, futs, serial_outs):
        rows = f.result(0)
        assert np.array_equal(rows, full[w.targets]), (
            f"{model}: microbatched result differs from the full forward"
        )
        assert np.array_equal(rows, s_out), (
            f"{model}: microbatched result differs from the serial loop"
        )

    # serving dispatch accounting: one query dispatch per block, nothing
    # else — no Python NA dispatch, no mesh lookups, no retraces
    assert dispatch["query_calls"] == stats.blocks, dispatch
    assert dispatch["graph_calls"] == 0, dispatch
    assert dispatch["mesh_lookups"] == 0, dispatch
    assert dispatch["traces"] == 0, dispatch

    mean_batch = stats.valid_slots / stats.blocks
    speedup = t_serial / t_micro
    emit(
        f"serve_{model}_serial", t_serial / len(wl) * 1e6,
        f"forwards={serial_stats.blocks};" + _stats_derived(serial_stats),
    )
    emit(
        f"serve_{model}_micro", t_micro / len(wl) * 1e6,
        f"speedup_vs_serial={speedup:.2f}x;blocks={stats.blocks}"
        f";mean_batch={mean_batch:.1f}"
        f";pad_fraction={stats.pad_fraction:.2f}"
        f";parity=bit_exact;" + _stats_derived(stats),
    )
    assert mean_batch >= 8, (
        f"{model}: saturation workload only packed mean batch "
        f"{mean_batch:.1f} — the ≥ 2x claim is vacuous below 8"
    )
    if assert_speedup:
        assert speedup >= 2.0, (
            f"{model}: microbatched serving only {speedup:.2f}x over "
            f"serial at mean batch {mean_batch:.1f} (need ≥ 2x)"
        )


def bench_multitenant(model: str, scale: float):
    """Two weight versions through ONE donate_params executable — the
    weight-streaming plane re-uploads fresh buffers per block, so tenant
    routing costs a device_put, not a recompile."""
    cfg = FlowConfig("fused", prune_k=PRUNE_K)
    task = pipeline.prepare(
        model, "imdb", scale=scale, max_degree=64, seed=0
    )
    init = task.params
    trained = pipeline.train_hgnn(task, steps=10, lr=5e-3)
    sess = task.compile(cfg)
    ref = {
        "init": np.asarray(sess(init)),
        "trained": np.asarray(sess(trained)),
    }
    with warnings.catch_warnings():
        # CPU backends cannot donate (XLA warns at lowering); the
        # contract under test is tenant routing, not buffer reuse
        warnings.filterwarnings("ignore", message=".*donated.*")
        sess_d = task.compile(cfg, donate_params=True)
    plane = WeightPlane(init, stream=True)
    plane.publish("init", init)
    plane.publish("trained", trained)

    wl = make_workload(
        N_REQUESTS, task.batch.num_targets, rate=None, size_range=(1, 4),
        tenants=("init", "trained"), seed=1,
    )
    with warnings.catch_warnings():
        # CPU backends cannot donate; the contract under test is routing
        warnings.filterwarnings("ignore", message=".*donated.*")
        fe = ServeFrontend(
            sess_d, plane, POLICY, clock=SystemClock(),
            executor=InlineExecutor(),
        )
        run_workload(fe, wl)  # warm
        fe = ServeFrontend(
            sess_d, plane, POLICY, clock=SystemClock(),
            executor=InlineExecutor(),
        )
        t0 = time.perf_counter()
        futs = run_workload(fe, wl)
        t_mt = time.perf_counter() - t0
    for w, f in zip(wl, futs):
        assert np.array_equal(f.result(0), ref[w.tenant][w.targets]), (
            f"{model}: tenant {w.tenant!r} served the wrong weights"
        )
    emit(
        f"serve_{model}_multitenant_stream", t_mt / len(wl) * 1e6,
        f"tenants=2;blocks={fe.stats.blocks};donate_params=True"
        f";parity=bit_exact_per_tenant",
    )


def bench_sharded(model: str, scale: float):
    """Front-end over the 8-way mesh-sharded session: microbatched
    results must stay bit-identical to the single-device full forward."""
    cfg = FlowConfig("fused_kernel", prune_k=PRUNE_K)
    task = pipeline.prepare(
        model, "imdb", scale=scale, max_degree=64, seed=0
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
        wl = make_workload(
            32, task.batch.num_targets, rate=None, size_range=(1, 4),
            seed=2,
        )
        with _frontend(sess, params) as fe:
            run_workload(fe, wl)  # warm
        fe = _frontend(sess, params)
        _reset_counters()
        t0 = time.perf_counter()
        futs = run_workload(fe, wl)
        t_micro = time.perf_counter() - t0
        assert flows.DISPATCH["graph_calls"] == 0
        assert flows.DISPATCH["mesh_lookups"] == 0
        assert flows.DISPATCH["query_calls"] == fe.stats.blocks
        for w, f in zip(wl, futs):
            assert np.array_equal(f.result(0), ref[w.targets]), (
                f"{model}: sharded microbatched result differs from the "
                f"single-device full forward"
            )
    emit(
        f"serve_sharded_8way_{model}", t_micro / len(wl) * 1e6,
        f"blocks={fe.stats.blocks};parity=bit_identical"
        f";python_dispatch_per_block=1",
    )


def main(smoke: bool = False, sharded: bool = False):
    models = ["rgat"] if smoke else ["han", "rgat", "simple_hgn"]
    scale = 0.06
    for model in models:
        bench_model(model, scale, assert_speedup=True)
    if not smoke:
        bench_multitenant("rgat", scale)
    if len(jax.devices()) >= 8:
        for model in models if not smoke else ["rgat"]:
            bench_sharded(model, scale)
    elif sharded:
        raise SystemExit(
            "--sharded needs >= 8 devices "
            "(XLA_FLAGS=--xla_force_host_platform_device_count=8)"
        )
    else:
        print("(single-device runtime: sharded-serving rows skipped)")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--smoke", action="store_true",
        help="one model, all asserts — the CI microbatching regression gate",
    )
    ap.add_argument(
        "--sharded", action="store_true",
        help="fail instead of skipping when < 8 devices are available "
        "(the CI multidevice job sets this)",
    )
    main(**vars(ap.parse_args()))
