"""Public wrappers used by ``repro.core.flows`` / ``repro.core.attention``.

``fused_prune_aggregate`` runs the flat (T, D) kernel pair;
``fused_prune_aggregate_grouped`` runs every degree bucket of a
``BucketedSemanticGraph`` in ONE kernel pair over the ragged grouped grid
(see ``kernel.py``); ``fused_prune_aggregate_grouped_sharded`` runs the
same grouped grid partitioned across a device mesh — ONE kernel pair *per
shard* under ``shard_map``, each shard walking only its own row blocks of
the ``ShardedBucketLayout``, with θ_*v gathers local to the shard and one
all-gather of the per-shard outputs before the global inverse-permutation
gather restores target order. Both grouped paths tile a graph at the
width ``tile_shape`` reads from its buckets, so the sharded launch stays
bit-identical to the single-device one. Device mirrors of a graph's static tile
stack and the per-``prune_k`` metadata tables are cached on its
``GroupedBucketLayout`` / ``ShardedBucketLayout`` so repeated layers/steps
ship no host arrays.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.distributed import sharding as dist
from repro.kernels.fused_prune_aggregate.kernel import (
    D_TILE,
    DISPATCH,
    T_TILE,
    fused_prune_aggregate_grouped_pallas,
    fused_prune_aggregate_pallas,
)

# The grouped layout's D-tile width, chosen per semantic graph from its
# buckets (``tile_shape``): the width of WIDTHS that minimises
# C_STEP_US·steps + C_SLOT_US·slots, K1's grid steps and the padded slots
# the θ gathers and tile transposes before it move. Both constants were
# measured on a TPU v5e (benchmarks/k1_tile_sweep.py): a merge step costs
# 3.3-3.6 µs at every width; a padded slot 2.7 ns of glue on HAN DBLP and
# 6.8 ns with Simple-HGN ACM's edge-type add, whose mean is taken.
WIDTHS = (8, 16, 32, 64, 128)
C_STEP_US = 3.4  # one K1 grid step: K merge rounds, whatever the width
C_SLOT_US = 0.0045  # one padded edge slot of NA glue (θ gather, transpose)


def fused_prune_aggregate(
    h_proj: jax.Array,  # (N, H, dh)
    theta_src: jax.Array,  # (N, H)
    theta_dst: jax.Array,  # (T, H)
    nbr_idx: jax.Array,  # (T, D)
    nbr_mask: jax.Array,  # (T, D)
    theta_rel: Optional[jax.Array] = None,  # (R, H)
    edge_type: Optional[jax.Array] = None,  # (T, D)
    prune_k: Optional[int] = None,
    slope: float = 0.2,
) -> jax.Array:
    t, d = nbr_idx.shape
    k = prune_k if prune_k is not None else d
    with tracing.counted("k1_steps", -(-t // T_TILE) * -(-d // D_TILE)):
        # The scalar pass: θ_u* per edge slot. 4·H bytes/edge instead of
        # the 4·H·dh bytes/edge feature row the staged flow gathers.
        theta_g = theta_src[nbr_idx]
        if theta_rel is not None and edge_type is not None:
            theta_g = theta_g + theta_rel[edge_type]
        return fused_prune_aggregate_pallas(
            theta_g, nbr_mask, theta_dst, nbr_idx, h_proj,
            prune_k=k, slope=slope,
        )


def tile_shape(sg, t_tile: int = T_TILE) -> tuple:
    """``(t_tile, w)`` of ``sg``'s grouped layout: among ``WIDTHS``, the
    width of least ``C_STEP_US·steps + C_SLOT_US·slots``, counted from the
    buckets' row blocks and capacities (the narrower width on a tie)."""
    blocks = np.array([-(-b.num_targets // t_tile) for b in sg.buckets], np.int64)
    caps = np.array([b.capacity for b in sg.buckets], np.int64)

    def cost(w):
        steps = int((blocks * np.maximum(-(-caps // w), 1)).sum())
        return C_STEP_US * steps + C_SLOT_US * steps * t_tile * w

    return t_tile, min(WIDTHS, key=cost)


def grouped_meta(layout, prune_k: Optional[int]):
    """Per-grid-step metadata + scratch width for a grouped launch.

    ``k_eff`` per bucket is ``min(prune_k, capacity)`` (the capacity where
    there is no pruning), whatever the tile width; buckets with capacity ≤
    ``prune_k`` (all, unpruned) take the §4.3 bypass. The shared scratch
    width ``k_s`` is the max effective K across buckets that actually
    contribute grid steps (empty buckets don't widen anything).

    Returns ``(k1_meta, k2_meta, k_s)``: K1 rows are (row_block, dt, n_dt,
    bypass, k_eff) per prune step; K2 rows are (grouped_row, slot) per
    gather step — each grouped row contributes exactly its own bucket's
    k_eff steps, so the ragged gather never pays the shared width.
    """
    caps = layout.caps.astype(np.int64)
    if prune_k is None:
        bypass = np.ones_like(caps)
        k_eff = caps
    else:
        bypass = (caps <= prune_k).astype(np.int64)
        k_eff = np.minimum(prune_k, caps)
    present = np.unique(layout.step_bucket)
    k_s = int(k_eff[present].max()) if len(present) else 1
    meta = np.stack(
        [
            layout.step_row,
            layout.step_dt,
            layout.step_ndt,
            bypass[layout.step_bucket],
            k_eff[layout.step_bucket],
        ]
    ).astype(np.int32)
    # per grouped row: its bucket's k_eff (row blocks appear in step_row
    # with their owning bucket; padded rows share the bucket's k_eff and
    # accumulate zeros)
    n_blocks = layout.num_rows // layout.t_tile
    block_bucket = np.zeros(n_blocks, np.int64)
    block_bucket[layout.step_row] = layout.step_bucket
    k_row = np.repeat(k_eff[block_bucket], layout.t_tile)
    starts = np.concatenate([[0], np.cumsum(k_row)[:-1]])
    slots = np.arange(int(k_row.sum())) - np.repeat(starts, k_row)
    agg_meta = np.stack(
        [np.repeat(np.arange(layout.num_rows), k_row), slots]
    ).astype(np.int32)
    return meta, agg_meta, k_s


def _layout_device(layout, prune_k: Optional[int]):
    """jnp mirrors of the layout's static arrays, cached on the layout."""
    cache = getattr(layout, "_dev", None)
    # eager conversion even when first reached inside an outer jit trace —
    # cached tracers would leak out of that trace
    with jax.ensure_compile_time_eval():
        if cache is None:
            cache = {
                "base": (
                    jnp.asarray(layout.nbr),
                    jnp.asarray(layout.msk.astype(np.int32)),
                    jnp.asarray(layout.ety),
                    jnp.asarray(layout.row_targets),
                    jnp.asarray(layout.perm),
                )
            }
            layout._dev = cache
        if prune_k not in cache:
            meta, agg_meta, k_s = grouped_meta(layout, prune_k)
            cache[prune_k] = (jnp.asarray(meta), jnp.asarray(agg_meta), k_s)
    return cache["base"], cache[prune_k]


@functools.partial(
    jax.jit,
    static_argnames=("k_s", "t_tile", "w", "slope", "use_rel"),
)
def _grouped_call(
    h_proj, theta_src, theta_dst, theta_rel,
    nbr, msk, ety, row_targets, meta, agg_meta, perm,
    k_s, t_tile, w, slope, use_rel,
):
    DISPATCH["grouped_traces"] += 1
    theta_g = theta_src[nbr]  # (G, t_tile, w, H)
    if use_rel:
        theta_g = theta_g + theta_rel[ety]
    h = theta_dst.shape[-1]
    td_rows = theta_dst[row_targets].reshape(-1, t_tile, h)
    return fused_prune_aggregate_grouped_pallas(
        theta_g, msk, nbr, td_rows, meta, agg_meta, h_proj, perm,
        k_s=k_s, t_tile=t_tile, w=w, slope=slope,
    )


def fused_prune_aggregate_grouped(
    h_proj: jax.Array,  # (N, H, dh)
    theta_src: jax.Array,  # (N, H)
    theta_dst: jax.Array,  # (T, H) — full target range of the graph
    sg,  # BucketedSemanticGraph
    theta_rel: Optional[jax.Array] = None,  # (R, H)
    prune_k: Optional[int] = None,
    slope: float = 0.2,
) -> jax.Array:
    """NA over ALL buckets of ``sg`` as one kernel-pair launch, over the
    tiles ``tile_shape`` reads from ``sg``.

    Returns ``(sg.num_targets, H, dh)`` float32 in target order.
    """
    t_tile, w = tile_shape(sg)
    layout = sg.grouped(t_tile, w)
    n, h, dh = h_proj.shape
    if layout.num_steps == 0:
        return jnp.zeros((sg.num_targets, h, dh), h_proj.dtype)
    (nbr, msk, ety, row_targets, perm), (meta, agg_meta, k_s) = _layout_device(
        layout, prune_k
    )
    use_rel = theta_rel is not None
    with tracing.counted("k1_steps", layout.num_steps):
        return _grouped_call(
            h_proj, theta_src, theta_dst,
            theta_rel if use_rel else None,
            nbr, msk, ety, row_targets, meta, agg_meta, perm,
            k_s=k_s, t_tile=t_tile, w=w, slope=slope, use_rel=use_rel,
        )


def _sharded_device(sl, prune_k: Optional[int]):
    """Stacked jnp mirrors of a ``ShardedBucketLayout``, cached on it.

    SPMD needs every shard to run the same program on same-shaped operands,
    so per-shard stacks are equalized: grid steps pad to one more than the
    max shard's count with filler steps aimed at the reserved pad block
    (all-masked tiles — the merge takes none of them and the pad block's
    flush writes zero α and −1 ids), K2 gather steps pad with ``(pad_row,
    slot 0)`` entries that accumulate that zero α into a row no target's
    ``perm`` entry reads, and the retention-scratch width ``k_s`` is the max
    across shards (narrower shards drop the extra slots at the flush
    exactly like narrow buckets do, so per-target arithmetic — and its bit
    pattern — matches the single-device launch).
    """
    cache = sl._dev
    t_tile, w, n_sh = sl.t_tile, sl.w, sl.n_shards
    # every shard gets at least one filler step, so K1 writes the pad
    # block in every shard: K2's padding steps read its ids, and K1 outputs
    # no step writes are uninitialised on a TPU (a DMA from such an id ran
    # out of bounds and halted the chip)
    g_max = sl.num_steps_max + 1
    pad_block = sl.pad_block
    pad_row = sl.num_rows_alloc - 1
    with jax.ensure_compile_time_eval():
        if "base" not in cache:
            nbr = np.zeros((n_sh, g_max, t_tile, w), np.int32)
            msk = np.zeros((n_sh, g_max, t_tile, w), np.int32)
            ety = np.zeros((n_sh, g_max, t_tile, w), np.int32)
            row_targets = np.zeros((n_sh, sl.num_rows_alloc), np.int32)
            for s, sh in enumerate(sl.shards):
                g = sh.num_steps
                nbr[s, :g] = sh.nbr
                msk[s, :g] = sh.msk.astype(np.int32)
                ety[s, :g] = sh.ety
                row_targets[s, : sh.num_rows] = sh.row_targets
            cache["base"] = (
                jnp.asarray(nbr), jnp.asarray(msk), jnp.asarray(ety),
                jnp.asarray(row_targets), jnp.asarray(sl.perm),
            )
        if prune_k not in cache:
            metas, aggs, k_s = [], [], 1
            for sh in sl.shards:
                if sh.num_steps:
                    m, a, k = grouped_meta(sh, prune_k)
                else:
                    m = np.zeros((5, 0), np.int32)
                    a = np.zeros((2, 0), np.int32)
                    k = 1
                metas.append(m)
                aggs.append(a)
                k_s = max(k_s, k)
            s_max = max(max(a.shape[1] for a in aggs), 1)
            meta = np.zeros((n_sh, 5, g_max), np.int32)
            agg = np.zeros((n_sh, 2, s_max), np.int32)
            for s, (m, a) in enumerate(zip(metas, aggs)):
                g, n_pad = m.shape[1], g_max - m.shape[1]
                meta[s, :, :g] = m
                # filler K1 steps: one pad block of n_pad D-tiles, bypass
                # off, k_eff 1 — flushes zero α at its last step
                meta[s, :, g:] = np.stack(
                    [
                        np.full(n_pad, pad_block),
                        np.arange(n_pad),
                        np.full(n_pad, n_pad),
                        np.zeros(n_pad, np.int64),
                        np.ones(n_pad, np.int64),
                    ]
                ).astype(np.int32)
                agg[s, :, : a.shape[1]] = a
                agg[s, 0, a.shape[1]:] = pad_row  # slot stays 0
            cache[prune_k] = (jnp.asarray(meta), jnp.asarray(agg), k_s)
    return cache["base"], cache[prune_k]


def fused_prune_aggregate_grouped_sharded(
    h_proj: jax.Array,  # (N, H, dh)
    theta_src: jax.Array,  # (N, H)
    theta_dst: jax.Array,  # (T, H) — full target range, replicated
    sg,  # BucketedSemanticGraph
    mesh,  # concrete jax.sharding.Mesh
    axis: str,  # mesh axis to shard over (the ``bucket_tiles`` rule axis)
    theta_rel: Optional[jax.Array] = None,  # (R, H)
    prune_k: Optional[int] = None,
    slope: float = 0.2,
) -> jax.Array:
    """NA over ALL buckets of ``sg``, partitioned across ``mesh[axis]``.

    ONE kernel-pair launch per shard per semantic graph: the shard_map body
    traces a single grouped ``pallas_call`` pair that every device runs on
    its own row-block slice of the tile stack. θ_u* and h' stay replicated
    (NA gathers arbitrary global source ids); each shard gathers only its
    own θ_*v rows; the per-shard outputs are all-gathered ONCE and the
    global inverse permutation restores target order. Bit-identical to the
    single-device grouped launch. Returns ``(sg.num_targets, H, dh)`` f32.
    """
    n_sh = mesh.shape[axis]
    t_tile, w = tile_shape(sg)
    sl = sg.sharded(n_sh, t_tile, w)
    n, h, dh = h_proj.shape
    if sl.num_steps_max == 0:
        return jnp.zeros((sg.num_targets, h, dh), jnp.float32)
    (nbr, msk, ety, row_targets, perm), (meta, agg_meta, k_s) = _sharded_device(
        sl, prune_k
    )
    use_rel = theta_rel is not None
    fn = _sharded_fn(mesh, axis, use_rel, k_s, t_tile, w, slope)
    args = (nbr, msk, ety, row_targets, meta, agg_meta, h_proj, theta_src,
            theta_dst) + ((theta_rel,) if use_rel else ())
    # every shard runs the longest shard's steps plus one pad-block step
    with tracing.counted("k1_steps", n_sh * (sl.num_steps_max + 1)):
        out = fn(*args)
    # the single all-gather: (S, rows_alloc, H, dh) -> replicated, then one
    # global inverse-permutation gather back to target order
    out = dist.replicate(out, mesh)
    return out.reshape(n_sh * sl.num_rows_alloc, h, dh)[perm]


@functools.lru_cache(maxsize=None)
def _sharded_fn(mesh, axis, use_rel, k_s, t_tile, w, slope):
    """The jitted shard_map body for one (mesh, static-config) pair.

    Cached on those statics so repeated layers/steps reuse one callable —
    jit's trace cache keys on function identity, and a fresh shard_map
    closure per call would retrace (and recompile) every NA dispatch.
    """
    from jax.sharding import PartitionSpec as P

    def body(nbr_s, msk_s, ety_s, rt_s, meta_s, agg_s, h_r, ts_r, td_r, *rel):
        DISPATCH["sharded_traces"] += 1
        h = td_r.shape[-1]
        # leading shard dim of the stacked operands is 1 inside the body
        theta_g = ts_r[nbr_s[0]]  # (G, t_tile, w, H) — local gather
        if use_rel:
            theta_g = theta_g + rel[0][ety_s[0]]
        td_rows = td_r[rt_s[0]].reshape(-1, t_tile, h)  # θ_*v local gather
        out = fused_prune_aggregate_grouped_pallas(
            theta_g, msk_s[0], nbr_s[0], td_rows, meta_s[0], agg_s[0], h_r,
            None, k_s=k_s, t_tile=t_tile, w=w, slope=slope,
        )
        return out[None]  # (1, num_rows_alloc, H, dh)

    sharded, rep = P(axis), P()
    in_specs = (sharded,) * 6 + (rep, rep, rep) + ((rep,) if use_rel else ())
    # repro: allow(jit-in-traced) -- lru_cache on the statics above means
    # this wrapper is built once per (mesh, config), not per call
    return jax.jit(dist.shard_map_call(body, mesh, in_specs, P(axis)))
