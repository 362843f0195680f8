"""The Pruner (paper §5.2) as a Pallas TPU kernel.

Streaming top-K selection over per-target neighbor scores: the retention
domain (scores + slot ids) lives in VMEM scratch and is carried across the
neighbor-tile grid dimension; each arriving element runs one Algorithm-1
step (compare against the domain minimum, replace-or-discard) as a
vectorized one-hot select, lane-parallel over a tile of targets.

VMEM budget per program: (Tt, Dt) score tile + 2×(Tt, K) retention domain
≈ 8·128·4 + 2·8·K·4 bytes — a few KiB; Dt=128 aligns the streaming tile to
the lane width, Tt=8 to the f32 sublane count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common
from repro.kernels.common import NEG, min_replace

T_TILE = 8
D_TILE = 128


def _pruner_kernel(scores_ref, mask_ref, out_s_ref, out_i_ref, rd_s, rd_i):
    d_idx = pl.program_id(1)

    @pl.when(d_idx == 0)
    def _init():
        rd_s[...] = jnp.full_like(rd_s, NEG)
        rd_i[...] = jnp.full_like(rd_i, -1)

    s = jnp.where(mask_ref[...] != 0, scores_ref[...], NEG)  # (Tt, Dt)
    base = d_idx * D_TILE

    def step(j, _):
        cur = jax.lax.dynamic_slice_in_dim(s, j, 1, axis=1)[:, 0]  # (Tt,)
        cur_id = (base + j).astype(jnp.int32)
        ids = jnp.full(cur.shape, cur_id, jnp.int32)
        new_s, (new_i,) = min_replace(rd_s[...], [(rd_i[...], ids)], cur, None)
        rd_s[...] = new_s
        rd_i[...] = new_i
        return 0

    jax.lax.fori_loop(0, D_TILE, step, 0)

    @pl.when(d_idx == pl.num_programs(1) - 1)
    def _flush():
        out_s_ref[...] = rd_s[...]
        out_i_ref[...] = jnp.where(rd_s[...] <= NEG / 2, -1, rd_i[...])


@functools.partial(jax.jit, static_argnames=("k",))
def topk_select_pallas(
    scores: jax.Array,  # (T, D) f32
    mask: jax.Array,  # (T, D) bool/int
    k: int,
) -> tuple[jax.Array, jax.Array]:
    t, d = scores.shape
    tp = (-t) % T_TILE
    dp = (-d) % D_TILE
    s = jnp.pad(scores.astype(jnp.float32), ((0, tp), (0, dp)))
    m = jnp.pad(mask.astype(jnp.int32), ((0, tp), (0, dp)))
    tt, dd = s.shape
    grid = (tt // T_TILE, dd // D_TILE)
    out_s, out_i = pl.pallas_call(
        _pruner_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((T_TILE, D_TILE), lambda i, j: (i, j)),
            pl.BlockSpec((T_TILE, D_TILE), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((T_TILE, k), lambda i, j: (i, 0)),
            pl.BlockSpec((T_TILE, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tt, k), jnp.float32),
            jax.ShapeDtypeStruct((tt, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((T_TILE, k), jnp.float32),
            pltpu.VMEM((T_TILE, k), jnp.int32),
        ],
        interpret=common.interpret_mode(),
    )(s, m)
    return out_s[:t], out_i[:t]
