"""HGNN serving on the ``repro.serve`` microbatching front-end.

The HGNN sibling of ``repro.launch.serve`` (the LM serving launcher):
build a task, train briefly, ``task.compile(flow)`` ONE executable, then
replay a seeded open-loop request stream (``repro.serve.load`` — the same
generator the load-test harness and ``benchmarks/serve_load.py`` use)
through three serving paths and report p50/p95 latency + throughput:

  * the serial one-request-at-a-time loop (one padded query dispatch per
    request — the pre-front-end baseline);
  * the inline microbatched front-end (saturation regime: requests pack
    into capacity-bucketed query blocks, one forward per BLOCK);
  * the threaded front-end (collector + double-buffered stepper threads,
    Poisson arrivals at ``--rate`` req/s — the production shape).

All three produce bit-identical logits; the deltas are pure batching.
``--ego`` reroutes primary blocks through the ego-subgraph path
(``session.query_ego``: O(neighborhood) forwards, 1e-5 parity instead of
bit-exact, dispatch counters reported after the microbatched run).

    PYTHONPATH=src python examples/hgnn_serve.py --model rgat --flow fused \
        --requests 64
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro import compile_cache
from repro.core import flows, pipeline
from repro.core.flows import FlowConfig
from repro.serve import (
    BatchPolicy,
    InlineExecutor,
    ServeFrontend,
    SystemClock,
    ThreadExecutor,
    make_workload,
    run_serial,
    run_workload,
)


def _report(name, stats, wall=None):
    s = stats.summary()
    qps = s["requests"] / wall if wall else s["qps"]
    print(f"[serve] {name:22s} p50 {s['p50_ms']:7.2f} ms   "
          f"p95 {stats.percentile(95) * 1e3:7.2f} ms   {qps:7.1f} req/s   "
          f"mean batch {s['mean_batch']:5.1f}  "
          f"({s['blocks']} blocks, pad {s['pad_fraction']:.0%})")


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="rgat",
                    choices=("han", "rgat", "simple_hgn"))
    ap.add_argument("--dataset", default="imdb")
    ap.add_argument("--flow", default="fused",
                    choices=("staged", "staged_pruned", "fused", "fused_kernel"))
    ap.add_argument("--prune-k", type=int, default=8)
    ap.add_argument("--scale", type=float, default=0.06)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--ego", action="store_true",
                    help="route primary blocks through the ego-subgraph "
                         "path (O(neighborhood) forwards, 1e-5 parity)")
    ap.add_argument("--rate", type=float, default=2000.0,
                    help="Poisson arrival rate (req/s) for the threaded run")
    ap.add_argument("--deltas", type=int, default=0, metavar="N",
                    help="stream N edge batches mid-serve through "
                         "repro.stream, printing per-batch merge latency "
                         "vs a cold layout rebuild")
    ap.add_argument("--train-steps", type=int, default=30)
    args = ap.parse_args()

    cfg = FlowConfig(args.flow, prune_k=args.prune_k)
    task = pipeline.prepare(args.model, args.dataset, scale=args.scale,
                            max_degree=64, seed=0)
    print(f"[serve] {task.name}: {task.num_edges} edges, "
          f"{len(task.sgs)} semantic graphs")
    params = pipeline.train_hgnn(task, steps=args.train_steps, lr=5e-3)

    t0 = time.perf_counter()
    sess = task.compile(cfg)
    full = np.asarray(sess(params))
    print(f"[serve] session compiled in {time.perf_counter() - t0:.2f}s "
          f"({sess!r})")

    policy = BatchPolicy(capacities=(1, 4, 8, 16), flush_timeout=2e-3,
                         ego=args.ego)
    wl = make_workload(args.requests, task.batch.num_targets, rate=None,
                       size_range=(1, 4), seed=0)

    def check(got, want):
        # the ego program is a different XLA fusion over the same math:
        # 1e-5 parity there, bit-exact everywhere else
        if args.ego:
            np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
        else:
            assert np.array_equal(got, want)

    # serial baseline: every request pays its own forward
    run_serial(sess, params, wl, policy, SystemClock())  # warm
    t0 = time.perf_counter()
    serial_outs, serial_stats = run_serial(
        sess, params, wl, policy, SystemClock()
    )
    t_serial = time.perf_counter() - t0
    _report("serial loop", serial_stats, t_serial)

    # microbatched, inline-driven (saturation regime)
    for k in ("query_calls", "ego_calls", "ego_bypass", "ego_fallback"):
        flows.DISPATCH[k] = 0
    fe = ServeFrontend(sess, params, policy, clock=SystemClock(),
                       executor=InlineExecutor())
    if args.ego:
        run_workload(fe, wl)  # warm the per-signature ego executables
        for k in ("query_calls", "ego_calls", "ego_bypass", "ego_fallback"):
            flows.DISPATCH[k] = 0
    t0 = time.perf_counter()
    futs = run_workload(fe, wl)
    t_micro = time.perf_counter() - t0
    _report("microbatched (inline)", fe.stats, t_micro)
    for w, f, s_out in zip(wl, futs, serial_outs):
        check(f.result(0), full[w.targets])
        if not args.ego:
            assert np.array_equal(f.result(0), s_out)  # pure batching
    print(f"[serve] microbatching speedup: {t_serial / t_micro:.1f}x "
          f"({serial_stats.blocks} forwards -> {fe.stats.blocks} blocks, "
          f"{flows.DISPATCH['query_calls']} Python dispatches)")
    if args.ego:
        d = flows.DISPATCH
        print(f"[serve] ego routing: {d['ego_calls']} ego blocks "
              f"({d['ego_bypass']} through the prune-K bypass), "
              f"{d['ego_fallback']} full-forward fallbacks, "
              f"~{sess.ego_planner.stats.rows_per_query:.1f} rows "
              f"touched/query vs {task.batch.total_nodes} graph rows")

    # threaded front-end under paced Poisson arrivals
    wl_paced = make_workload(args.requests, task.batch.num_targets,
                             rate=args.rate, size_range=(1, 4), seed=1)
    with ServeFrontend(sess, params, policy, clock=SystemClock(),
                       executor=ThreadExecutor()) as fe_t:
        run_workload(fe_t, wl_paced)
    _report(f"threaded @{args.rate:.0f}/s", fe_t.stats)

    # multi-tenant: trained + initial weights through one executable
    from repro.serve import WeightPlane
    plane = WeightPlane(params)
    plane.publish("trained", params)
    plane.publish("init", task.params)
    fe_mt = ServeFrontend(sess, plane, policy, clock=SystemClock(),
                          executor=InlineExecutor())
    wl_mt = make_workload(args.requests, task.batch.num_targets, rate=None,
                          tenants=("trained", "init"), seed=2)
    futs = run_workload(fe_mt, wl_mt)
    ref = {"trained": full, "init": np.asarray(sess(task.params))}
    for w, f in zip(wl_mt, futs):
        check(f.result(0), ref[w.tenant][w.targets])
    print(f"[serve] multi-tenant: {fe_mt.stats.blocks} single-tenant blocks "
          f"served 2 weight versions through one executable")

    # live graph evolution: edge deltas merged in while traffic flows
    if args.deltas:
        from repro.stream import StreamIngestor
        from repro.stream.merge import _rebuild_all

        ing = StreamIngestor(task, sess)
        fe_s = ServeFrontend(ing.plane, params, policy,
                             clock=SystemClock(), executor=InlineExecutor())
        rng = np.random.default_rng(4)
        t0 = time.perf_counter()
        _rebuild_all(ing.sgs, ing.graph, task.sgb_kind,
                     metapaths=task.metapaths, add_self_loops=True,
                     cap_fanout=4096, **task.sgb_args)
        t_cold = time.perf_counter() - t0
        merges = []
        for i in range(args.deltas):
            g = ing.graph
            s_t, rel, d_t = g.relations[i % len(g.relations)]
            rep = ing.ingest({rel: (
                rng.integers(0, g.num_nodes[s_t], 8),
                rng.integers(0, g.num_nodes[d_t], 8),
            )})
            merges.append(rep.t_merge)
            print(f"[serve] delta #{rep.seq} -> v{rep.version}: +8 {rel} "
                  f"edges, merge {rep.t_merge * 1e3:.2f} ms "
                  f"[{rep.stats.summary()}]")
            for _ in range(2):  # traffic interleaved with every merge
                fe_s.submit(rng.integers(0, task.batch.num_targets, 2))
            fe_s.pump(force=True)
        fe_s.close()
        st = fe_s.stats
        assert st.failed == 0 and st.shed == 0 and st.expired == 0
        print(f"[serve] live deltas: {args.deltas} batches merged mid-serve; "
              f"mean merge {np.mean(merges) * 1e3:.2f} ms vs "
              f"{t_cold * 1e3:.2f} ms cold rebuild "
              f"({np.mean(merges) / t_cold:.2f}x); {st.completed} requests "
              f"served across {ing.version} version swaps, 0 failed")


if __name__ == "__main__":
    main()
