"""Execution-flow configuration shared by all HGNN models.

``flow``:
  * ``staged``        — traditional baseline (no pruning)
  * ``staged_pruned`` — separate pruning pass then staged NA (Fig. 3 setup)
  * ``fused``         — ADE operation-fusion flow (scan-tiled jnp)
  * ``fused_kernel``  — ADE flow via the Pallas kernel (Mosaic on TPU,
                        interpret mode on CPU)

Two entry points: ``run_aggregate`` operates on raw padded-CSC arrays;
``run_aggregate_graph`` accepts either a flat ``SemanticGraph`` or a
degree-bucketed ``BucketedSemanticGraph``.

Bucketed NA is SINGLE-DISPATCH: one call per semantic graph, not one per
bucket. ``fused_kernel`` routes to the grouped ragged-grid kernel — every
bucket in ONE ``pallas_call`` pair, driven by the graph's
``GroupedBucketLayout`` — and the jnp flows trace all buckets into one jit
region that gathers θ_*v once into bucket-concatenation order, hands each
bucket a contiguous view, and restores target order with the precomputed
inverse-permutation gather (no per-bucket ``out.at[targets].set`` scatters,
no per-bucket O(T) score gathers). Buckets whose capacity is ≤ ``prune_k``
still hit the paper's §4.3 pruner bypass — inside the kernel (a direct
slot copy) or via the static per-bucket routing in ``run_aggregate``.

``FlowConfig.bucket_dispatch="loop"`` keeps the legacy one-dispatch-per-
bucket path (eager Python loop + per-bucket scatters) for benchmarks and
golden parity tests; see ``benchmarks/na_dispatch.py``.

MULTI-DEVICE: when a mesh with a ``bucket_tiles`` rule axis (the
``("data",)`` inference mesh) is ambient, ``fused_kernel`` bucketed NA
shards transparently — the graph's ``ShardedBucketLayout`` partitions the
grouped tile stack by target row blocks, ``shard_map`` runs ONE kernel
pair per shard with shard-local θ_*v gathers, and a single all-gather +
the global inverse permutation restore target order (bit-identical to the
single-device launch; see ``benchmarks/na_sharded.py``). With no ambient
mesh — or ``FlowConfig.shard="off"`` — nothing changes.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp

from repro.core import attention
from repro.core.hetgraph import BucketedSemanticGraph, SemanticGraph
from repro.distributed import sharding as dist

# Python-side dispatch accounting (reset by benchmarks):
#   graph_calls   — run_aggregate_graph entries on bucketed graphs
#   bucket_calls  — per-bucket NA dispatches issued by the legacy loop path
#   traces        — retraces of the single-dispatch jit region
#   sharded_calls — bucketed NA dispatches routed to the mesh-sharded path
#   mesh_lookups  — ambient-mesh resolutions (dist.graph_mesh walks) paid by
#                   NA dispatch. Hoisted: models open one mesh_scope() per
#                   apply (≤ 1 lookup per forward, not one per semantic
#                   graph), and an InferenceSession pins the mesh it
#                   resolved at build time (0 lookups, even while tracing).
#   query_calls   — query-block executable dispatches
#                   (InferenceSession.query). The serving amortization
#                   evidence: a microbatching front-end serves N requests
#                   with ~N/capacity of these, the serial loop pays N.
#   ego_calls     — ego-subgraph executable dispatches
#                   (InferenceSession.query_ego): the forward ran on the
#                   extracted O(neighborhood) batch, not the full graph.
#   ego_bypass    — ego dispatches whose per-graph neighbor capacity fit
#                   under the pruner's K, so the compiled program routed
#                   every semantic graph through the §4.3 pruner bypass.
#   ego_fallback  — ego queries whose closure exceeded the top ego
#                   capacity and fell back to the full-forward query path.
#   ego_traces    — per-ego-signature AOT compiles (the ego analogue of
#                   ``traces``; steady-state serving should stop paying
#                   these once the signature ladder is warm).
DISPATCH = {
    "graph_calls": 0, "bucket_calls": 0, "traces": 0, "sharded_calls": 0,
    "mesh_lookups": 0, "query_calls": 0, "ego_calls": 0, "ego_bypass": 0,
    "ego_fallback": 0, "ego_traces": 0,
}

# mesh-resolution scope stack, held in a ContextVar so concurrent traces
# (a serving thread building a session while another traces eagerly) each
# see their own stack; entries are one-slot lazy caches
# [resolved: bool, graph_mesh() result or None]
_UNSET = object()
_MESH_SCOPE: contextvars.ContextVar = contextvars.ContextVar(
    "repro_mesh_scope", default=()
)


@contextlib.contextmanager
def mesh_scope(pinned=_UNSET):
    """Scope within which the ambient graph mesh is resolved at most once.

    With no argument, pushes a LAZY slot: the first NA dispatch inside the
    scope that needs the mesh resolves it (one ``DISPATCH["mesh_lookups"]``
    tick) and every later dispatch reuses the result. Models wrap each
    ``apply`` in one of these. A no-arg scope opened inside an existing
    scope reuses the enclosing slot (so a pinning caller wins over the
    model's own lazy scope).

    With ``pinned=<graph_mesh() result or None>``, pushes a PRE-RESOLVED
    slot: no lookup ever happens inside, even at trace time — this is how
    an ``InferenceSession`` locks NA to the mesh it resolved once at
    session build.
    """
    stack = _MESH_SCOPE.get()
    if pinned is _UNSET and stack:
        yield  # reuse the enclosing scope's slot
        return
    entry = [pinned is not _UNSET, None if pinned is _UNSET else pinned]
    token = _MESH_SCOPE.set(stack + (entry,))
    try:
        yield
    finally:
        _MESH_SCOPE.reset(token)


def _graph_mesh_once():
    """The scope-cached ``dist.graph_mesh()``. Outside any scope, resolves
    every call (the unhoisted legacy behavior, still counted)."""
    stack = _MESH_SCOPE.get()
    if stack:
        entry = stack[-1]
        if not entry[0]:
            # repro: allow(dispatch-in-traced) -- trace-time tick is the point
            DISPATCH["mesh_lookups"] += 1
            entry[1] = dist.graph_mesh()
            entry[0] = True
        return entry[1]
    # repro: allow(dispatch-in-traced) -- trace-time tick is the point
    DISPATCH["mesh_lookups"] += 1
    return dist.graph_mesh()


@dataclasses.dataclass(frozen=True)
class FlowConfig:
    flow: str = "staged"
    prune_k: Optional[int] = None
    tile: int = 128
    # "single": one dispatch per semantic graph (grouped kernel / one jit
    # region). "loop": legacy per-bucket loop, kept for benchmarks/parity.
    bucket_dispatch: str = "single"
    # "auto": fused_kernel bucketed NA shard_maps over the ambient mesh's
    # bucket_tiles axis when one is present (no-op without a mesh).
    # "off": always the single-device path, mesh or not.
    shard: str = "auto"

    def __post_init__(self):
        assert self.flow in ("staged", "staged_pruned", "fused", "fused_kernel")
        assert self.bucket_dispatch in ("single", "loop")
        assert self.shard in ("auto", "off")


def run_aggregate(
    cfg: FlowConfig,
    h_proj: jax.Array,
    scores: attention.DecomposedScores,
    nbr_idx,
    nbr_mask,
    edge_type=None,
) -> jax.Array:
    if cfg.flow == "staged":
        return attention.aggregate_staged(
            h_proj, scores, nbr_idx, nbr_mask, edge_type, prune_k=None
        )
    if cfg.flow == "staged_pruned":
        return attention.aggregate_staged(
            h_proj, scores, nbr_idx, nbr_mask, edge_type, prune_k=cfg.prune_k
        )
    # paper §4.3: targets with |N(v)| <= K bypass the pruner entirely (the
    # retention domain is a no-op there). Static per-graph routing: when the
    # whole padded table fits under K, the fused flow IS the plain
    # aggregation — run it without the retention-domain machinery. Under the
    # bucketed layout this fires per bucket, not per graph.
    if cfg.prune_k is not None and cfg.prune_k >= nbr_idx.shape[1]:
        return attention.aggregate_staged(
            h_proj, scores, nbr_idx, nbr_mask, edge_type, prune_k=None
        )
    # clamp the streaming tile to the padded width: a capacity-32 bucket
    # must not be padded out to a 128-wide tile (the streaming top-k merge
    # is tile-size invariant, so this is a pure FLOPs/memory saving)
    return attention.aggregate_fused(
        h_proj, scores, nbr_idx, nbr_mask, edge_type,
        prune_k=cfg.prune_k, tile=min(cfg.tile, nbr_idx.shape[1]),
        use_kernel=(cfg.flow == "fused_kernel"),
    )


def _device_tables(sg: BucketedSemanticGraph, use_ety: bool):
    """jnp mirrors of the bucket tables + concat order + inverse perm,
    cached on the graph so repeated layers/steps ship no host arrays."""
    key = ("tables", use_ety)
    if key not in sg._device:
        # the first call may come from inside an outer jit trace (training
        # step); force eager conversion so the cache holds concrete arrays,
        # not tracers
        with jax.ensure_compile_time_eval():
            tables = tuple(
                (
                    jnp.asarray(b.nbr_idx),
                    jnp.asarray(b.nbr_mask),
                    jnp.asarray(b.edge_type) if use_ety else None,
                )
                for b in sg.buckets
                if b.num_targets > 0
            )
            sg._device[key] = (
                tables,
                jnp.asarray(sg.concat_targets()),
                jnp.asarray(sg.target_perm()),
            )
    return sg._device[key]


@functools.partial(jax.jit, static_argnames=("cfg",))
def _bucketed_aggregate(cfg, h_proj, scores, tables, order, perm):
    """All buckets of one semantic graph in ONE jit region.

    θ_*v is gathered once into bucket-concatenation order; each bucket gets
    a contiguous view of it (static slice, no per-bucket gather); the
    concatenated result returns to target order with a single
    inverse-permutation gather.
    """
    DISPATCH["traces"] += 1
    ordered = attention.DecomposedScores(
        scores.theta_src, scores.theta_dst[order], scores.theta_rel
    )
    outs, off = [], 0
    for nbr, msk, ety in tables:
        t_b = nbr.shape[0]
        sc = attention.narrow_targets(ordered, off, t_b)
        outs.append(run_aggregate(cfg, h_proj, sc, nbr, msk, ety))
        off += t_b
    return jnp.concatenate(outs, axis=0)[perm]


def run_aggregate_graph_bucket_loop(
    cfg: FlowConfig,
    h_proj: jax.Array,
    scores: attention.DecomposedScores,
    sg: BucketedSemanticGraph,
) -> jax.Array:
    """LEGACY per-bucket dispatch: one NA call, one full-table θ_*v gather,
    and one ``out.at[targets].set`` scatter per bucket, driven by an eager
    Python loop. Superseded by the single-dispatch path; kept as the
    benchmark baseline (``benchmarks/na_dispatch.py``) and parity oracle.
    """
    use_ety = scores.theta_rel is not None
    _, h, dh = h_proj.shape
    out = jnp.zeros((sg.num_targets, h, dh), h_proj.dtype)
    for b in sg.buckets:
        # repro: allow(dispatch-in-traced) -- trace-time tick is the point
        DISPATCH["bucket_calls"] += 1
        targets = jnp.asarray(b.targets)
        z = run_aggregate(
            cfg, h_proj, attention.slice_targets(scores, targets),
            jnp.asarray(b.nbr_idx), jnp.asarray(b.nbr_mask),
            jnp.asarray(b.edge_type) if use_ety else None,
        )
        out = out.at[targets].set(z)
    return out


def run_aggregate_graph(
    cfg: FlowConfig,
    h_proj: jax.Array,
    scores: attention.DecomposedScores,
    sg: Union[SemanticGraph, BucketedSemanticGraph],
) -> jax.Array:
    """NA over a semantic graph. Returns (num_targets, H, dh).

    ``scores.theta_dst`` must cover the graph's full target range (one row
    per ``dst_type`` vertex, in local order). Bucketed graphs run as one
    dispatch (see module docstring) unless ``cfg.bucket_dispatch="loop"``.
    Its ops carry the compile-time scope ``na.<graph name>``; the kernel
    pair inside it adds ``k1`` and ``k2``.
    """
    with jax.named_scope(f"na.{sg.name}"):
        return _aggregate_graph(cfg, h_proj, scores, sg)


def _aggregate_graph(cfg, h_proj, scores, sg) -> jax.Array:
    use_ety = scores.theta_rel is not None
    if isinstance(sg, BucketedSemanticGraph):
        # repro: allow(dispatch-in-traced) -- trace-time tick is the point
        DISPATCH["graph_calls"] += 1
        if cfg.bucket_dispatch == "loop":
            return run_aggregate_graph_bucket_loop(cfg, h_proj, scores, sg)
        if cfg.flow == "fused_kernel":
            from repro.kernels.fused_prune_aggregate import ops as k_ops

            # the kernel accumulates in f32; cast back like the loop path's
            # at[].set into an h_proj.dtype buffer, so the dispatch switch
            # never changes the output dtype
            gm = _graph_mesh_once() if cfg.shard == "auto" else None
            if gm is not None:
                mesh, axis, _ = gm
                # repro: allow(dispatch-in-traced) -- trace-time tick is the point
                DISPATCH["sharded_calls"] += 1
                return k_ops.fused_prune_aggregate_grouped_sharded(
                    h_proj, scores.theta_src, scores.theta_dst, sg, mesh,
                    axis, theta_rel=scores.theta_rel, prune_k=cfg.prune_k,
                    slope=attention.LEAKY_SLOPE,
                ).astype(h_proj.dtype)
            return k_ops.fused_prune_aggregate_grouped(
                h_proj, scores.theta_src, scores.theta_dst, sg,
                theta_rel=scores.theta_rel, prune_k=cfg.prune_k,
                slope=attention.LEAKY_SLOPE,
            ).astype(h_proj.dtype)
        tables, order, perm = _device_tables(sg, use_ety)
        if not tables:
            _, h, dh = h_proj.shape
            return jnp.zeros((sg.num_targets, h, dh), h_proj.dtype)
        return _bucketed_aggregate(cfg, h_proj, scores, tables, order, perm)
    return run_aggregate(
        cfg, h_proj, scores,
        jnp.asarray(sg.nbr_idx), jnp.asarray(sg.nbr_mask),
        jnp.asarray(sg.edge_type) if use_ety else None,
    )
