"""ADE-style pruned decode attention — the paper's technique on LM serving.

Single-token decode against a long KV cache is the transformer analog of
neighbor aggregation: the cache rows are the neighbor features, q·k logits
are the attention coefficients, and attention disparity is extreme at long
context. The kernel streams the cache in tiles, maintains a per-(batch,head)
K-slot retention domain (logit + position) in VMEM — Algorithm 1 verbatim —
then softmaxes over the retained set; a scalar-prefetch second kernel
fetches exactly K value rows per (batch, head) and accumulates α·v.

HBM traffic per step: S·dh (keys, streamed for scoring) + K·dh (values)
instead of 2·S·dh — and with the optional quantized-score first pass
(ops.py) the key pass shrinks too. GQA is supported: the retention domain
is per q-head; cache tiles are read once per kv-head and broadcast to the
group's q-heads in VREGs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common
from repro.kernels.common import NEG, min_replace

S_TILE = 128


def _score_prune_kernel(
    q_ref,  # (1, H, dh)
    k_ref,  # (1, St, Hkv, dh)
    len_ref,  # (1, 1) int32 valid cache length for this row
    alpha_ref,  # out (1, H, K)
    ids_ref,  # out (1, H, K)
    rd_s,  # scratch (H, K)
    rd_i,  # scratch (H, K)
    *,
    scale: float,
    group: int,
):
    s_idx = pl.program_id(1)

    @pl.when(s_idx == 0)
    def _init():
        rd_s[...] = jnp.full_like(rd_s, NEG)
        rd_i[...] = jnp.full_like(rd_i, -1)

    q = q_ref[0]  # (H, dh)
    kt = k_ref[0]  # (St, Hkv, dh)
    h, dh = q.shape
    hkv = kt.shape[1]
    # logits (H, St): q-head h attends kv-head h // group
    qg = q.reshape(hkv, group, dh)
    logits = jnp.einsum("ksd,kgd->kgs", kt.transpose(1, 0, 2), qg) * scale
    logits = logits.reshape(h, -1)  # (H, St)
    base = s_idx * S_TILE
    valid_len = len_ref[0, 0]
    pos = base + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    logits = jnp.where(pos < valid_len, logits, NEG)

    def step(j, _):
        cur = jax.lax.dynamic_slice_in_dim(logits, j, 1, axis=1)[:, 0]  # (H,)
        cur_id = jnp.full((h,), base + j, jnp.int32)
        new_s, (new_i,) = min_replace(rd_s[...], [(rd_i[...], cur_id)], cur, None)
        rd_s[...] = new_s
        rd_i[...] = new_i
        return 0

    jax.lax.fori_loop(0, S_TILE, step, 0)

    @pl.when(s_idx == pl.num_programs(1) - 1)
    def _flush():
        valid = rd_s[...] > NEG / 2
        lg = jnp.where(valid, rd_s[...], NEG)
        mx = jnp.max(lg, axis=1, keepdims=True)
        ex = jnp.where(valid, jnp.exp(lg - mx), 0.0)
        alpha_ref[0] = ex / (ex.sum(axis=1, keepdims=True) + 1e-30)
        ids_ref[0] = jnp.where(valid, rd_i[...], -1)


def _value_gather_kernel(ids_ref, alpha_ref, v_ref, out_ref, *, group: int):
    b, h, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = alpha_ref[0, 0, k]
    out_ref[...] += a * v_ref[0, 0, 0, :][None, None, :]


@functools.partial(
    jax.jit, static_argnames=("prune_k", "scale")
)
def topk_decode_attention_pallas(
    q: jax.Array,  # (B, H, dh)
    k_cache: jax.Array,  # (B, S, Hkv, dh)
    v_cache: jax.Array,  # (B, S, Hkv, dh)
    lengths: jax.Array,  # (B,) valid prefix lengths
    prune_k: int,
    scale: float | None = None,
) -> jax.Array:
    b, h, dh = q.shape
    _, s, hkv, _ = k_cache.shape
    group = h // hkv
    kk = min(prune_k, s)
    if scale is None:
        scale = dh ** -0.5
    sp = (-s) % S_TILE
    k_cache = jnp.pad(k_cache.astype(jnp.float32), ((0, 0), (0, sp), (0, 0), (0, 0)))
    ss = k_cache.shape[1]
    lens = lengths.astype(jnp.int32).reshape(b, 1)

    alpha, ids = pl.pallas_call(
        functools.partial(_score_prune_kernel, scale=scale, group=group),
        grid=(b, ss // S_TILE),
        in_specs=[
            pl.BlockSpec((1, h, dh), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, S_TILE, hkv, dh), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (i, 0), memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, h, kk), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, h, kk), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, kk), jnp.float32),
            jax.ShapeDtypeStruct((b, h, kk), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((h, kk), jnp.float32),
            pltpu.VMEM((h, kk), jnp.int32),
        ],
        interpret=common.interpret_mode(),
    )(q.astype(jnp.float32), k_cache, lens)

    ids_safe = jnp.maximum(ids, 0)
    # kv-head lookup folded into the prefetch table: (B, H, K) -> row in S
    out = pl.pallas_call(
        functools.partial(_value_gather_kernel, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h, kk),
            in_specs=[
                pl.BlockSpec((1, 1, kk), lambda i, j, l, ids: (i, j, 0)),
                pl.BlockSpec(
                    (1, 1, 1, dh),
                    lambda i, j, l, ids: (i, ids[i, j, l], j // group, 0),
                ),
            ],
            out_specs=pl.BlockSpec((1, 1, dh), lambda i, j, l, ids: (i, j, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, dh), jnp.float32),
        interpret=common.interpret_mode(),
    )(ids_safe, alpha, jnp.pad(v_cache.astype(jnp.float32), ((0, 0), (0, sp), (0, 0), (0, 0))))
    return out
