"""ADE fused Neighbor Aggregation — the paper's operation-fusion flow on TPU.

Two chained Pallas kernels inside one jit region (mirroring the ASIC's
pruner → aggregation-engine pipeline through the attention/edge buffers):

K1  ``prune``: streams per-edge decomposed coefficients θ_u* (+ relation
    term) in neighbor tiles, maintains the K-slot retention domain (ranking
    scalar, per-head θ vector, slot id) in VMEM scratch, and at the last
    tile applies LeakyReLU(θ_u*+θ_*v), masks, and softmaxes over the
    retained set — emitting attention weights α (H,T,K) and slot ids (T,K).
    Pruned neighbors never have their importance computed (paper §4.1) and
    their feature rows are never read.

K2  ``gather-aggregate``: scalar-prefetch (PrefetchScalarGridSpec) kernel;
    the retained *global source ids* drive the BlockSpec index_map, so each
    grid step DMAs exactly one retained feature row HBM→VMEM and
    accumulates α·h'_u into the output block. Only K rows per target are
    ever fetched — this is the paper's DRAM-access saving (Fig. 8).

The full (T, D, H·dh) gathered-feature tensor of the staged flow is never
materialized anywhere.

Layout, chosen so Mosaic compiles it: every tile keeps targets in sublanes
and neighbor slots in lanes, and per-head arrays carry the head as a
leading axis (θ tiles (H, Tt, W), retained θ (H, Tt, K), α (H, T, K)). A
tile's column is read with a masked lane max, never a dynamic lane slice.
K2's per-step scalars are three 1-D vectors (row, slot, source id).

Two grid shapes share the K1 body and the K2 kernel:

  * **flat** (``fused_prune_aggregate_pallas``): one ``(T, D)`` padded-CSC
    table, rectangular grid ``(T/T_TILE, D/D_TILE)``.
  * **grouped ragged** (``fused_prune_aggregate_grouped_pallas``): every
    degree bucket of a ``BucketedSemanticGraph`` in ONE launch. The 1-D
    grid walks a ``GroupedBucketLayout``'s tile stack (bucket-major,
    row-tile next, D-tile innermost); a scalar-prefetched metadata table
    tells each step its output row block, its D-tile position (first →
    reset scratch, last → softmax + flush), its bucket's effective K, and
    whether the bucket takes the §4.3 pruner **bypass** branch
    (capacity ≤ K: candidate tiles are copied straight into their
    statically-known retention slots — no min-replace scan). Buckets with
    different capacities share one scratch of width K_s = max effective K;
    slots past a row's own K are parked at +inf (``POS``) so the
    retention-domain argmin never selects them. Narrow buckets therefore
    run fewer D-tile steps instead of padding to the global D_max, and the
    whole semantic graph costs one ``pallas_call`` pair instead of one per
    bucket.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common
from repro.kernels.common import NEG, POS, argmin_onehot

T_TILE = 8
D_TILE = 128
# grouped ragged grid: D-tile width. Narrow so capacity-8/16/32 buckets pay
# at most w-1 padded slots per row, at the price of K1 tiles that fill 8 of
# a vreg's 128 lanes.
W_TILE = 8

# trace-time launch accounting: how many pallas_call sites were traced and
# how often the grouped single-dispatch region retraced. After
# jax.clear_caches() + one forward, "pallas_calls" equals the number of
# kernel launches that forward dispatches — up to jit-cache sharing between
# identically-shaped call sites, which traces once but launches per call
# (count per-graph with a cleared cache when exactness matters).
DISPATCH = {"pallas_calls": 0, "grouped_traces": 0, "sharded_traces": 0}


def _pick(x: jax.Array, lane: jax.Array, j) -> jax.Array:
    """Column ``j`` of ``x`` along its lane (last) axis, kept as a size-1
    axis. A masked max, exact for every value: Mosaic has no dynamic lane
    slice, and this serves a traced ``j`` and a static one alike."""
    floating = np.issubdtype(x.dtype, np.floating)
    fill = -np.inf if floating else np.iinfo(x.dtype).min
    return jnp.max(jnp.where(lane == j, x, fill), axis=-1, keepdims=True)


def _prune_body(
    dt,  # D-tile index of this step within its row block
    n_dt,  # D-tiles of the row block
    k_eff,  # effective K of the row block; slots past it park at +inf
    bypass,  # §4.3 direct-copy flag, or None where no step can set it
    theta_ref,  # (H, Tt, W) θ_u* (+rel) per edge slot, head-major
    mask_ref,  # (Tt, W) int32
    gid_ref,  # (Tt, W) int32 global source ids
    theta_dst_ref,  # (Tt, H) θ_*v of the row block
    alpha_ref,  # out (H, Tt, K) softmax weights of the retained slots
    ids_ref,  # out (Tt, K) retained global ids (-1 = empty)
    rd_rank,  # scratch (Tt, K) f32 ranking scalar per retained slot
    rd_theta,  # scratch (H, Tt, K) f32 per-head θ_u* per retained slot
    rd_id,  # scratch (Tt, K) i32
    *,
    slope: float,
):
    """K1 on one (Tt, W) tile: Algorithm-1 retention-domain update, and at
    the row block's last D-tile the masked LeakyReLU softmax over it.

    Every array keeps targets in sublanes and slots in lanes; per-head
    arrays carry the head as a leading axis. The column walk is a
    ``fori_loop`` whose candidate column is a masked lane max (``_pick``),
    so no step needs a dynamic lane slice — the layout Mosaic compiles."""
    h, t_tile, w = theta_ref.shape
    k = rd_rank.shape[-1]
    slot = jax.lax.broadcasted_iota(jnp.int32, (t_tile, k), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (t_tile, w), 1)

    @pl.when(dt == 0)
    def _init():
        rd_rank[...] = jnp.where(slot < k_eff, NEG, POS)
        rd_theta[...] = jnp.zeros_like(rd_theta)
        rd_id[...] = jnp.full_like(rd_id, -1)

    theta = theta_ref[...]  # (H, Tt, W)
    valid = mask_ref[...] != 0
    rank = jnp.where(valid, theta.sum(0), NEG)  # (Tt, W)
    gids = jnp.where(valid, gid_ref[...], -1)

    def walk(update):
        def step(j, carry):
            rr, ri, rt = carry
            cur = _pick(rank, col, j)  # (Tt, 1)
            at = update(j, rr, cur)  # (Tt, K) slot receiving column j
            return (
                jnp.where(at, cur, rr),
                jnp.where(at, _pick(gids, col, j), ri),
                jnp.where(at[None], _pick(theta, col[None], j), rt),
            )

        rr, ri, rt = jax.lax.fori_loop(
            0, w, step, (rd_rank[...], rd_id[...], rd_theta[...])
        )
        rd_rank[...] = rr
        rd_id[...] = ri
        rd_theta[...] = rt

    def replace_min(j, rr, cur):
        # strict '>' keeps the incumbent on ties; the FIRST minimum goes
        onehot, m = argmin_onehot(rr)
        return onehot & (cur > m)

    # static guard: a bypass bucket's k_eff is its padded capacity (≥ w), so
    # K < w proves no step sets the flag — and the w slots the copy fills
    # would not fit the scratch (pl.when still traces untaken branches)
    if bypass is not None and k >= w:

        @pl.when(bypass != 0)
        def _direct():
            # §4.3 pruner bypass, in-kernel: capacity ≤ K means every
            # candidate is retained, so its slot is known statically from
            # the tile column — a straight copy, no argmin per column
            walk(lambda j, rr, cur: slot == dt * w + j)

        @pl.when(bypass == 0)
        def _insert():
            walk(replace_min)

    else:
        walk(replace_min)

    @pl.when(dt == n_dt - 1)
    def _flush():
        ok = (rd_rank[...] > NEG / 2) & (slot < k_eff)  # (Tt, K)
        # θ_*v per head as (H, Tt, 1): the diagonal of (H, Tt, H)
        cube = (h, t_tile, h)
        th_dst = _pick(
            jnp.broadcast_to(theta_dst_ref[...][None], cube),
            jax.lax.broadcasted_iota(jnp.int32, cube, 2),
            jax.lax.broadcasted_iota(jnp.int32, cube, 0),
        )
        th = rd_theta[...] + th_dst  # (H, Tt, K)
        th = jnp.where(th >= 0, th, slope * th)  # LeakyReLU
        th = jnp.where(ok[None], th, NEG)
        mx = jnp.max(th, axis=-1, keepdims=True)
        ex = jnp.where(ok[None], jnp.exp(th - mx), 0.0)
        alpha_ref[...] = ex / (ex.sum(axis=-1, keepdims=True) + 1e-30)
        ids_ref[...] = jnp.where(ok, rd_id[...], -1)


def _prune_kernel(*refs, k_eff: int, slope: float):
    # flat grid (T/Tt, D/Dt): every row block prunes to the same K
    d_idx = pl.program_id(1)
    _prune_body(d_idx, pl.num_programs(1), k_eff, None, *refs, slope=slope)


def _grouped_prune_kernel(meta_ref, *refs, slope: float):
    # ragged 1-D grid: meta rows (row_block, dt, n_dt, bypass, k_eff)
    g = pl.program_id(0)
    _prune_body(
        meta_ref[1, g], meta_ref[2, g], meta_ref[4, g], meta_ref[3, g],
        *refs, slope=slope,
    )


def _aggregate_kernel(row_ref, slot_ref, src_ref, alpha_ref, h_ref, out_ref):
    # 1-D grid: step s accumulates retention slot slot[s] of output row
    # row[s]; the index maps DMA that row's α (H, K) and the one feature
    # row h'[src[s]] (H, dh). Steps of a row are consecutive, from slot 0.
    del row_ref, src_ref  # index maps only
    slot = slot_ref[pl.program_id(0)]

    @pl.when(slot == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = alpha_ref[...]  # (H, K)
    lane = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    out_ref[...] += _pick(a, lane, slot) * h_ref[...]


def _gather_aggregate(alpha, ids, agg_row, agg_slot, h_proj):
    """K2: Σ_slot α·h'[id] per output row, one retained row DMA per step.

    ``alpha`` is K1's (H, rows, K) output, ``ids`` its (rows, K) slot ids;
    ``agg_row``/``agg_slot`` (S,) list the (row, slot) pairs to gather, each
    row's slots consecutive and starting at 0. Returns (rows, H, dh) f32.
    """
    h, rows, k = alpha.shape
    n, _, dh = h_proj.shape
    src = jnp.maximum(ids[agg_row, agg_slot], 0)  # α is 0 on empty slots
    DISPATCH["pallas_calls"] += 1
    return pl.pallas_call(
        _aggregate_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(agg_row.shape[0],),
            in_specs=[
                pl.BlockSpec(
                    (pl.Squeezed(), h, k), lambda s, r, sl, src: (r[s], 0, 0)
                ),
                pl.BlockSpec(
                    (pl.Squeezed(), h, dh), lambda s, r, sl, src: (src[s], 0, 0)
                ),
            ],
            out_specs=pl.BlockSpec(
                (pl.Squeezed(), h, dh), lambda s, r, sl, src: (r[s], 0, 0)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, h, dh), jnp.float32),
        interpret=common.interpret_mode(),
    )(agg_row, agg_slot, src, alpha.transpose(1, 0, 2), h_proj.astype(jnp.float32))


def _prune_scratch(t_tile: int, k: int, h: int):
    return [
        pltpu.VMEM((t_tile, k), jnp.float32),
        pltpu.VMEM((h, t_tile, k), jnp.float32),
        pltpu.VMEM((t_tile, k), jnp.int32),
    ]


@functools.partial(jax.jit, static_argnames=("prune_k", "slope"))
def fused_prune_aggregate_pallas(
    theta_g: jax.Array,  # (T, D, H)
    mask: jax.Array,  # (T, D)
    theta_dst: jax.Array,  # (T, H)
    nbr_idx: jax.Array,  # (T, D) global ids
    h_proj: jax.Array,  # (N, H, dh)
    prune_k: int,
    slope: float = 0.2,
) -> jax.Array:
    t, d, h = theta_g.shape
    k = min(prune_k, d)
    tp, dp = (-t) % T_TILE, (-d) % D_TILE
    theta_g = jnp.pad(theta_g.astype(jnp.float32), ((0, tp), (0, dp), (0, 0)))
    mask = jnp.pad(mask.astype(jnp.int32), ((0, tp), (0, dp)))
    theta_dst = jnp.pad(theta_dst.astype(jnp.float32), ((0, tp), (0, 0)))
    gid = jnp.pad(nbr_idx.astype(jnp.int32), ((0, tp), (0, dp)))
    tt, dd = mask.shape

    DISPATCH["pallas_calls"] += 1
    alpha, ids = pl.pallas_call(
        functools.partial(_prune_kernel, k_eff=k, slope=slope),
        grid=(tt // T_TILE, dd // D_TILE),
        in_specs=[
            pl.BlockSpec((h, T_TILE, D_TILE), lambda i, j: (0, i, j)),
            pl.BlockSpec((T_TILE, D_TILE), lambda i, j: (i, j)),
            pl.BlockSpec((T_TILE, D_TILE), lambda i, j: (i, j)),
            pl.BlockSpec((T_TILE, h), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((h, T_TILE, k), lambda i, j: (0, i, 0)),
            pl.BlockSpec((T_TILE, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((h, tt, k), jnp.float32),
            jax.ShapeDtypeStruct((tt, k), jnp.int32),
        ],
        scratch_shapes=_prune_scratch(T_TILE, k, h),
        interpret=common.interpret_mode(),
    )(theta_g.transpose(2, 0, 1), mask, gid, theta_dst)

    agg_row = jnp.repeat(jnp.arange(tt, dtype=jnp.int32), k)
    agg_slot = jnp.tile(jnp.arange(k, dtype=jnp.int32), tt)
    out = _gather_aggregate(alpha, ids, agg_row, agg_slot, h_proj)
    return out[:t]


@functools.partial(jax.jit, static_argnames=("k_s", "t_tile", "w", "slope"))
def fused_prune_aggregate_grouped_pallas(
    theta_g: jax.Array,  # (G, t_tile, w, H) grid-ordered θ_u* (+rel) tiles
    mask: jax.Array,  # (G, t_tile, w)
    gid: jax.Array,  # (G, t_tile, w) global source ids
    theta_dst_rows: jax.Array,  # (R, t_tile, H) θ_*v per grouped row
    meta: jax.Array,  # (5, G) int32 per-step K1 metadata (see kernel)
    agg_meta: jax.Array,  # (2, S) int32 per-step K2 (row, slot) metadata
    h_proj: jax.Array,  # (N, H, dh)
    perm: jax.Array,  # (T,) grouped row of each target; None = raw rows
    k_s: int,
    t_tile: int = T_TILE,
    w: int = W_TILE,
    slope: float = 0.2,
) -> jax.Array:
    """Single-launch NA over all buckets of a grouped layout.

    One K1 launch walks every bucket's tiles (ragged 1-D grid, scalar-
    prefetched metadata); one K2 launch gathers the retained feature rows
    (ragged too — each row contributes its own bucket's effective K steps,
    so the shared scratch width K_s never inflates the gather); the final
    gather by ``perm`` restores target order. Returns ``(T, H, dh)``
    float32. ``perm=None`` skips that gather and returns the raw grouped
    rows ``(R·t_tile, H, dh)`` — the sharded path runs one launch pair per
    shard in grouped-row order and applies ONE global inverse permutation
    after the shards' outputs are all-gathered.
    """
    grid_steps, _, _, h = theta_g.shape
    r = theta_dst_rows.shape[0]
    rows = r * t_tile
    sq = pl.Squeezed()

    DISPATCH["pallas_calls"] += 1
    alpha, ids = pl.pallas_call(
        functools.partial(_grouped_prune_kernel, slope=slope),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(grid_steps,),
            in_specs=[
                pl.BlockSpec((sq, h, t_tile, w), lambda g, m: (g, 0, 0, 0)),
                pl.BlockSpec((sq, t_tile, w), lambda g, m: (g, 0, 0)),
                pl.BlockSpec((sq, t_tile, w), lambda g, m: (g, 0, 0)),
                pl.BlockSpec((t_tile, h), lambda g, m: (m[0, g], 0)),
            ],
            out_specs=[
                pl.BlockSpec((h, t_tile, k_s), lambda g, m: (0, m[0, g], 0)),
                pl.BlockSpec((t_tile, k_s), lambda g, m: (m[0, g], 0)),
            ],
            scratch_shapes=_prune_scratch(t_tile, k_s, h),
        ),
        out_shape=[
            jax.ShapeDtypeStruct((h, rows, k_s), jnp.float32),
            jax.ShapeDtypeStruct((rows, k_s), jnp.int32),
        ],
        interpret=common.interpret_mode(),
    )(
        meta,
        theta_g.astype(jnp.float32).transpose(0, 3, 1, 2),
        mask.astype(jnp.int32),
        gid.astype(jnp.int32),
        theta_dst_rows.astype(jnp.float32).reshape(rows, h),
    )

    out = _gather_aggregate(alpha, ids, agg_meta[0], agg_meta[1], h_proj)
    return out if perm is None else out[perm]
