"""Streamed graph-delta merge vs cold SGB rebuild, under live traffic.

The ``repro.stream`` value proposition, measured and asserted (CI runs
``--smoke``; the committed trajectory lives in ``BENCH_deltas.json``):

  * MERGE COST: the mean per-batch ``apply_delta`` wall time (pure layout
    work: absorb into bucket slack, spill-rebuild only dirty slices,
    mirror only the layout keys the served stack carries) must be
    <= 0.2x one cold rebuild of the full stack (builders + grouped tile
    stacks for the same keys). Asserted at scale=1.0 (full run); the
    smoke emits the ratio without the floor. The workload is dblp with
    each batch streaming random edges into one of the two update-prone
    relations (authorship AP, venue PV) — the dominant TP slice (~56k
    edges at full scale) stays clean, so the merge pays only for the
    slice the batch actually dirtied (blast-radius confinement, the
    subsystem's designed win). Spill-tier batches are part of the
    measurement, not filtered out.
  * PARITY: after streaming every batch, the merged stack's logits are
    BIT-IDENTICAL to a from-scratch ``pipeline.prepare`` of the delta'd
    graph — always asserted, smoke included (the merge contract in
    ``repro.stream.merge`` is exact, not approximate).
  * SERVING PARITY: a ``ServeFrontend`` over the ingestor's
    ``GraphPlane`` serves query traffic interleaved with every ingest —
    zero failed / shed / expired requests across all version swaps.
  * EGO CONTINUITY: after an absorb-tier ingest dirtying one vertex
    outside a warm query's closure, re-running that query on the new
    version retraces NOTHING (``DISPATCH["ego_traces"]`` unchanged — the
    closure was carried and the executable adopted).

With >= 8 devices (``--sharded``): the same merge + parity + serving
loop against an 8-way mesh-sharded session, sharded splits mirrored by
the merge.

    PYTHONPATH=src:. python benchmarks/graph_deltas.py --smoke
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import time
import warnings

import jax
import numpy as np

from benchmarks.common import emit as _emit_to

emit = functools.partial(_emit_to, path="BENCH_deltas.json")

from repro.core import flows, pipeline
from repro.core.flows import FlowConfig
from repro.core.hetgraph import build_relation_graphs
from repro.serve import BatchPolicy, FakeClock, InlineExecutor, ServeFrontend
from repro.stream import StreamIngestor
from repro.stream.merge import _degrees_of

PRUNE_K = 8
MERGE_RATIO_CEILING = 0.2


STREAM_RELS = ("AP", "PV")  # update-prone dblp relations; TP stays clean


def _delta(rng, g, n, i):
    """Batch ``i``: random edges into ONE update-prone relation."""
    rels = [r for r in g.relations if r[1] in STREAM_RELS]
    s_t, name, d_t = rels[i % len(rels)]
    return {
        name: (
            rng.integers(0, g.num_nodes[s_t], n),
            rng.integers(0, g.num_nodes[d_t], n),
        )
    }


def _cold_rebuild_time(graph, old_sgs, sgb_args):
    """Wall time of the from-scratch layout path the merge replaces:
    the relation builders plus the SAME grouped/sharded tile-stack keys
    the served stack carries."""
    t0 = time.perf_counter()
    built = build_relation_graphs(
        graph,
        max_degree=sgb_args["max_degree"],
        seed=sgb_args["seed"],
        bucket_sizes=sgb_args["bucket_sizes"],
    )
    for old, new in zip(old_sgs, built):
        for key in old._grouped:
            new.grouped(*key)
        for key in old._sharded:
            new.sharded(*key)
    return time.perf_counter() - t0


def _absorbable_clean_target(ing, avoid):
    """A target id with bucket slack for one more edge, outside
    ``avoid`` — a delta to it is guaranteed absorb-tier and guaranteed
    not to dirty the avoided closure."""
    g = ing.graph
    s_t, rel, d_t = g.relations[0]
    sg = next(s for s in ing.sgs if s.name == rel)
    bucket_of, row_of = sg.row_lookup()
    cand = np.setdiff1d(
        np.arange(g.num_nodes[d_t], dtype=np.int64), avoid.get(d_t, [])
    )
    deg = _degrees_of(sg, cand, bucket_of, row_of)
    caps = np.asarray(sg.bucket_capacities)[bucket_of[cand]]
    ok = cand[deg + 1 <= caps]
    assert ok.size, "no absorbable target outside the closure"
    return rel, s_t, int(ok[0])


def bench_deltas(smoke: bool, sharded: bool = False):
    scale = 0.05 if smoke else 1.0
    n_batches = 4 if smoke else 8
    batch_edges = 8 if smoke else 48
    flow = (
        FlowConfig("fused_kernel", prune_k=PRUNE_K)
        if sharded
        else FlowConfig("fused", prune_k=PRUNE_K)
    )
    prefix = "deltas_sharded_8way" if sharded else "deltas"
    rng = np.random.default_rng(0)
    task = pipeline.prepare("rgat", "dblp", scale=scale, max_degree=None, seed=0)
    mesh = (
        jax.set_mesh(jax.sharding.Mesh(np.array(jax.devices()[:8]), ("data",)))
        if sharded
        else contextlib.nullcontext()
    )
    with mesh:
        sess = task.compile(flow)
        if sharded:
            info = sess.mesh_info
            assert info is not None and info[2] == 8, "no ambient 8-way mesh"
        sess.enable_ego(seed=0, sample_sizes=(1, 4))
        ing = StreamIngestor(task, sess)
        fe = ServeFrontend(
            ing.plane,
            task.params,
            policy=BatchPolicy(capacities=(1, 4)),
            clock=FakeClock(),
            executor=InlineExecutor(),
        )
        n_tgt = task.batch.num_targets
        futures = [fe.submit(rng.integers(0, n_tgt, 2)) for _ in range(2)]
        fe.pump(force=True)

        # -- ego continuity proof (one surgical absorb-tier ingest) --------
        qa = np.arange(min(4, n_tgt), dtype=np.int32)
        np.asarray(sess.query_ego(task.params, qa))  # warm trace + closure
        full_a, _ = sess.ego_planner._closure(qa.astype(np.int64))
        rel, s_t, tgt = _absorbable_clean_target(ing, full_a)
        traces0 = flows.DISPATCH["ego_traces"]
        rep = ing.ingest(
            {rel: (rng.integers(0, ing.graph.num_nodes[s_t], 1),
                   np.array([tgt], dtype=np.int64))}
        )
        assert rep.stats.absorbed_slices >= 1 and not rep.stats.full_rebuild, (
            rep.stats.summary()
        )
        np.asarray(ing.session.query_ego(task.params, qa))
        clean_retraces = flows.DISPATCH["ego_traces"] - traces0
        assert clean_retraces == 0, (
            f"clean ego closure retraced across the version swap "
            f"({clean_retraces} traces)"
        )
        hits = ing.session.ego_planner.stats.closure_hits
        assert hits >= 1, "carried closure was not hit"
        if not sharded:
            emit(
                "deltas_ego",
                None,
                "clean closure survives swap: 0 retraces, carried + adopted",
                clean_retraces=clean_retraces,
                closure_hits=hits,
                closures_carried=rep.closures_carried,
                exes_adopted=rep.exes_adopted,
            )

        # -- streamed batches under live traffic ---------------------------
        t_merge_total = 0.0
        absorbed = spilled = rebuilt = full_rebuilds = clean = 0
        for i in range(n_batches):
            r = ing.ingest(_delta(rng, ing.graph, batch_edges, i))
            t_merge_total += r.t_merge
            clean += r.stats.clean_slices
            absorbed += r.stats.absorbed_slices
            spilled += r.stats.spilled_slices
            rebuilt += r.stats.rebuilt_slices
            full_rebuilds += int(r.stats.full_rebuild)
            futures += [fe.submit(rng.integers(0, n_tgt, 2)) for _ in range(2)]
            fe.pump(force=True)
        fe.close()
        mean_merge = t_merge_total / n_batches

        # -- serving parity across every swap ------------------------------
        st = fe.stats
        assert st.failed == 0 and st.shed == 0 and st.expired == 0, (
            st.summary()
        )
        assert st.completed == st.submitted, st.summary()
        assert all(f.done() for f in futures), "stranded future"

        # -- cold rebuild of the final graph, and bit-parity ---------------
        t_cold = _cold_rebuild_time(ing.graph, ing.sgs, task.sgb_args)
        cold = pipeline.prepare(
            "rgat", ing.graph, max_degree=None, seed=0
        )
        ref = np.asarray(cold.compile(flow)(task.params))
        got = np.asarray(ing.session(task.params))
        assert np.array_equal(ref, got), (
            "merged stack logits are not bit-identical to the cold rebuild"
        )

    ratio = mean_merge / t_cold if t_cold > 0 else float("inf")
    if not smoke and ratio > MERGE_RATIO_CEILING:
        raise AssertionError(
            f"delta merge is not cheap enough: mean {mean_merge * 1e3:.2f}ms "
            f"vs cold rebuild {t_cold * 1e3:.2f}ms (ratio {ratio:.3f} > "
            f"{MERGE_RATIO_CEILING})"
        )
    emit(
        f"{prefix}_merge",
        mean_merge * 1e6,
        f"ratio={ratio:.4f};cold_ms={t_cold * 1e3:.2f};"
        f"batches={n_batches}x{batch_edges}edges",
        merge_vs_cold_ratio=ratio,
        cold_rebuild_ms=t_cold * 1e3,
        clean_slices=clean,
        absorbed_slices=absorbed,
        spilled_slices=spilled,
        rebuilt_slices=rebuilt,
        full_rebuilds=full_rebuilds,
    )
    emit(
        f"{prefix}_parity",
        None,
        "post-upgrade logits bit-identical to from-scratch build; zero "
        "failed/shed/expired across every version swap",
        bit_identical=1,
        versions_published=ing.version,
        served=st.completed,
        failed=st.failed,
        shed=st.shed,
        expired=st.expired,
    )


def main(smoke: bool = False, sharded: bool = False):
    if sharded and len(jax.devices()) < 8:
        raise SystemExit(
            "--sharded needs >= 8 devices "
            "(XLA_FLAGS=--xla_force_host_platform_device_count=8)"
        )
    bench_deltas(smoke, sharded=sharded)


if __name__ == "__main__":
    warnings.filterwarnings("ignore", category=UserWarning)
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--sharded", action="store_true")
    args = ap.parse_args()
    main(smoke=args.smoke, sharded=args.sharded)
