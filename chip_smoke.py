"""Start the ADE fused-kernel serving path on one TPU chip and check it.

The main path, through the entry points a user calls:

    pipeline.prepare("han", "dblp", scale=1.0)   # synthetic DBLP, seeded
    task.compile(FlowConfig("fused_kernel", prune_k=8))
    ServeFrontend(session, ...)                  # inline executor, no fallback

HAN runs at its own widths (8 heads x 8 dims, 64-dim features) with random
weights drawn from ``--seed``. The served rows are checked against two
references computed on the same device: the jnp ``fused`` flow at the same
``prune_k``, one program per degree bucket (within ``FUSED_RTOL``), and a
float32 ``staged_pruned`` forward at
``jax.default_matmul_precision("highest")``. Against the latter, the
default-precision session is reported (max|dlogits| and argmax
disagreement) and a second ``fused_kernel`` session compiled at "highest"
is gated (within ``STAGED_RTOL``).

    python chip_smoke.py                    # one chip
    python chip_smoke.py --four-chips       # 4-way sharded NA vs one device
    JAX_PLATFORMS=cpu python chip_smoke.py --scale 0.05   # CPU rehearsal

Every phase runs on any backend. An exception in a phase is printed with
its traceback on standard output and counts as a failed check. The last
line of standard output is ``{"ok": true, "device": {...}}`` only when
every check passed on a TPU; otherwise the script exits non-zero without
it. ``--four-chips`` runs only the sharded path and its one-device
comparison.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import compile_cache  # noqa: E402
from repro.core import pipeline  # noqa: E402
from repro.core.flows import FlowConfig  # noqa: E402
from repro.serve import (  # noqa: E402
    BatchPolicy,
    InlineExecutor,
    ServeFrontend,
    SystemClock,
    make_workload,
    run_workload,
)

MODEL, DATASET = "han", "dblp"
PRUNE_K = 8
CAPACITIES = (1, 4, 8, 16)
REQUESTS = 64
# fused_kernel vs the jnp fused flow: the same algorithm, both at the
# default matmul precision. They differ in how NA forms Σα·h' (f32
# multiply-adds in K2 against an XLA einsum, which the TPU may run at bf16
# pass precision), so the bound is relative to the logits' scale.
FUSED_RTOL = 1e-2
# fused_kernel compiled at "highest" vs the f32 staged_pruned reference at
# "highest": both rank neighbours by f32 scores, so they keep the same K
# neighbours and differ only in summation order. A single changed
# selection, or a kernel fault of more than a tenth of a percent, exceeds
# this. The default-precision session against the same reference is
# reported, not gated: its scores carry bf16 pass rounding, so near-ties at
# the K-th slot may retain other neighbours.
STAGED_RTOL = 1e-3


class Checks:
    """Named pass/fail records; the script fails if any failed."""

    def __init__(self):
        self.failed = []

    def __call__(self, ok: bool, what: str) -> None:
        print(f"[check] {'ok  ' if ok else 'FAIL'} {what}", flush=True)
        if not ok:
            self.failed.append(what)

    @contextlib.contextmanager
    def phase(self, name: str):
        """Run one phase; an exception in it is printed with its traceback
        on standard output and recorded as a failed check."""
        print(f"[phase] {name}", flush=True)
        try:
            yield
        except (Exception, SystemExit):
            traceback.print_exc(file=sys.stdout)
            self(False, f"{name}: raised")


def _graph_summary(task) -> None:
    g = task.graph
    print(f"[graph] {task.name}: nodes {dict(g.num_nodes)}, "
          f"{sum(len(s) for s, _ in g.edges.values())} raw edges, "
          f"features {({t: x.shape[1] for t, x in g.features.items()})}",
          flush=True)
    for sg in task.sgs:
        lay = sg.grouped()
        buckets = [(b.num_targets, b.capacity) for b in sg.buckets]
        print(f"[graph] semantic graph {sg.name}: {sg.num_targets} targets, "
              f"{sg.num_edges} edges, buckets (targets, capacity) {buckets}, "
              f"{lay.num_steps} grouped K1 steps", flush=True)


def _serve(sess, params, workload, check, label):
    """Serve ``workload`` inline through a front-end with no fallback
    session; return the served rows in workload order."""
    policy = BatchPolicy(capacities=CAPACITIES)
    fe = ServeFrontend(sess, params, policy, clock=SystemClock(),
                       executor=InlineExecutor())
    t0 = time.perf_counter()
    futs = run_workload(fe, workload)
    rows = [f.result(0) for f in futs]
    dt = time.perf_counter() - t0
    fe.close()
    answered = sum(f.done() for f in futs)
    print(f"[serve] {label}: {answered}/{len(workload)} requests answered in "
          f"{fe.stats.blocks} blocks, {dt!r} s host wall time", flush=True)
    check(answered == len(workload), f"{label}: every request answered")
    check(all(f.via == "primary" for f in futs),
          f"{label}: every future resolved via the primary engine")
    check(fe.stats.fallback_blocks == 0,
          f"{label}: fallback_blocks == 0 (got {fe.stats.fallback_blocks})")
    return np.concatenate(rows)


def _compile(task, flow, label):
    t0 = time.perf_counter()
    sess = task.compile(flow)
    logits = np.asarray(sess(task.params))
    dt = time.perf_counter() - t0
    print(f"[compile] {label}: {dt!r} s (lower + compile + first call)",
          flush=True)
    return sess, logits


def _mosaic_sites(sess, on_tpu, check, label):
    hlo = sess._executable.as_text()
    n = hlo.count('custom_call_target="tpu_custom_call"')
    print(f"[compile] {label}: {n} tpu_custom_call sites in the compiled HLO"
          f"{'' if on_tpu else ' (interpret mode off the TPU)'}", flush=True)
    if on_tpu:
        check(n >= 2, f"{label}: the Pallas kernels went through Mosaic")
    return hlo


def _compare(got, ref, rtol, check, label):
    scale = float(np.max(np.abs(ref)))
    diff = float(np.max(np.abs(got - ref)))
    flips = int(np.sum(np.argmax(got, -1) != np.argmax(ref, -1)))
    print(f"[parity] {label}: max|dlogits| {diff!r}, max|ref| {scale!r}, "
          f"argmax differs on {flips}/{len(ref)} targets", flush=True)
    check(np.isfinite(got).all(), f"{label}: served logits finite")
    if rtol is not None:
        check(diff <= rtol * scale,
              f"{label}: max|dlogits| <= {rtol} * max|ref|")
    return diff


def one_chip(args, check, on_tpu) -> None:
    flow = FlowConfig("fused_kernel", prune_k=PRUNE_K)
    with check.phase("build the task and compile the fused_kernel session"):
        t0 = time.perf_counter()
        task = pipeline.prepare(MODEL, DATASET, scale=args.scale,
                                seed=args.seed)
        print(f"[build] prepare: {time.perf_counter() - t0!r} s", flush=True)
        _graph_summary(task)
        sess, full = _compile(task, flow, "fused_kernel session")
        _mosaic_sites(sess, on_tpu, check, "fused_kernel session")
        check(full.shape == (task.batch.num_targets, task.graph.num_classes),
              f"forward logits shape {full.shape}")
    if check.failed:
        return

    rows = None
    wl = make_workload(REQUESTS, task.batch.num_targets, rate=None,
                       size_range=(1, 4), seed=args.seed)
    ids = np.concatenate([w.targets for w in wl])
    with check.phase("serve through ServeFrontend"):
        rows = _serve(sess, task.params, wl, check, "fused_kernel")
        check(np.array_equal(rows, full[ids]),
              "served rows are the session's full-forward rows, bit for bit")

    with check.phase("jnp fused reference"):
        # One program per degree bucket (bucket_dispatch="loop", dispatched
        # eagerly): on a TPU v5e the single-program bucketed fused NA of the
        # APA graph compiles but never finishes (refused by
        # session.refuse_known_tpu_hang), while each bucket's program runs.
        fused = FlowConfig("fused", prune_k=PRUNE_K, bucket_dispatch="loop")
        t0 = time.perf_counter()
        ref_fused = np.asarray(task.model.apply(task.params, task.batch, fused))
        print(f"[compile] jnp fused reference, per-bucket programs: "
              f"{time.perf_counter() - t0!r} s (compile + first call)",
              flush=True)
        if rows is not None:
            _compare(rows, ref_fused[ids], FUSED_RTOL, check,
                     "served rows vs jnp fused (same prune_k)")
        _compare(full, ref_fused, FUSED_RTOL, check,
                 "all targets vs jnp fused (same prune_k)")

    with check.phase("f32 staged_pruned reference at highest precision"):
        staged = FlowConfig("staged_pruned", prune_k=PRUNE_K)
        with jax.default_matmul_precision("highest"):
            ref_staged = np.asarray(jax.jit(
                lambda p: task.model.apply(p, task.batch, staged)
            )(task.params))
            _, full_hi = _compile(task, flow,
                                  "fused_kernel session at highest precision")
        _compare(full, ref_staged, None, check,
                 "default-precision session vs f32 staged_pruned at highest "
                 "(reported)")
        _compare(full_hi, ref_staged, STAGED_RTOL, check,
                 "highest-precision session vs f32 staged_pruned at highest")


def four_chips(args, check, on_tpu) -> None:
    devs = jax.devices()
    check(len(devs) >= 4, f"four devices present (got {len(devs)})")
    if len(devs) < 4:
        return
    flow = FlowConfig("fused_kernel", prune_k=PRUNE_K)

    with check.phase("one-device fused_kernel session"):
        task1 = pipeline.prepare(MODEL, DATASET, scale=args.scale,
                                 seed=args.seed)
        _graph_summary(task1)
        sess1, full1 = _compile(task1, flow, "one-device fused_kernel session")
        check(sess1.mesh_info is None, "one-device session runs without a mesh")

    with check.phase("4-way sharded fused_kernel session"):
        mesh = jax.sharding.Mesh(np.array(devs[:4]), ("data",))
        with jax.set_mesh(mesh):
            t0 = time.perf_counter()
            task4 = pipeline.prepare(MODEL, DATASET, scale=args.scale,
                                     seed=args.seed, shards=4)
            print(f"[build] sharded prepare: {time.perf_counter() - t0!r} s",
                  flush=True)
            sess4, full4 = _compile(task4, flow,
                                    "4-way sharded fused_kernel session")
        check(sess4.mesh_info is not None and sess4.mesh_info[2] == 4,
              "sharded session resolved the 4-way ('data',) mesh")
        hlo = _mosaic_sites(sess4, on_tpu, check, "4-way sharded session")
        check("num_partitions=4" in hlo,
              "sharded program is partitioned 4 ways (num_partitions=4)")
        for sg in task4.sgs:
            # each device's K1 launch reads its own shard of the stacked
            # (4, 5, G) step metadata: a (1, 5, G) slice in the partitioned
            # HLO; G is the longest shard's steps plus one pad-block step
            g = sg.sharded(4).num_steps_max + 1
            local, whole = f"s32[1,5,{g}]" in hlo, f"s32[4,5,{g}]" in hlo
            print(f"[mesh] {sg.name}: shard-local K1 metadata s32[1,5,{g}] in "
                  f"the partitioned program: {local}; whole 4-shard stack also "
                  f"held on each device as a constant: {whole}", flush=True)
            check(local, f"{sg.name}: each device runs K1 on its own shard")
        out = sess4(task4.params)
        shard_devs = {s.device for s in out.addressable_shards}
        print(f"[mesh] sharded forward output on {len(shard_devs)} devices: "
              f"{sorted(d.id for d in shard_devs)}", flush=True)
        check(shard_devs == set(devs[:4]),
              "sharded forward ran on the four mesh devices, not on the first")
    if check.failed:
        return

    with check.phase("serve both sessions and compare"):
        wl = make_workload(REQUESTS, task1.batch.num_targets, rate=None,
                           size_range=(1, 4), seed=args.seed)
        rows1 = _serve(sess1, task1.params, wl, check, "one-device")
        rows4 = _serve(sess4, task4.params, wl, check, "4-way sharded")
        exact = bool(np.array_equal(rows1, rows4))
        print(f"[parity] 4-way sharded vs one device: bit-identical={exact}",
              flush=True)
        _compare(rows4, rows1, FUSED_RTOL, check,
                 "4-way sharded rows vs one-device rows")
        _compare(full4, full1, FUSED_RTOL, check,
                 "4-way sharded vs one-device, all targets")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", type=float, default=1.0,
                    help="synthetic DBLP scale (1.0 = the generator's "
                         "published sizes)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-way sharded path and its "
                         "one-device comparison")
    args = ap.parse_args()

    print(f"[cache] compilation cache: {compile_cache.enable()}", flush=True)
    devs = jax.devices()
    d0 = devs[0]
    print(f"[device] platform={d0.platform} kind={d0.device_kind!r} "
          f"count={len(devs)} jax={jax.__version__}", flush=True)
    on_tpu = d0.platform == "tpu"

    check = Checks()
    (four_chips if args.four_chips else one_chip)(args, check, on_tpu)

    if check.failed:
        print(f"[result] {len(check.failed)} check(s) failed", flush=True)
        return 1
    if not on_tpu:
        print(f"[result] every phase ran on {d0.platform!r}; no TPU, so no "
              "result line", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind, "count": len(devs),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
