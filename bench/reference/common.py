"""Plain float32 pieces shared by the references (imports nothing of the
program).

Matrix products go through ``dot``, at ``precision=HIGHEST``; every other
product is an elementwise multiply and a sum in float32. A control swaps
``dot`` for :func:`dot_bf16x3`, the three-pass bfloat16 product that JAX
calls ``"high"`` precision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -jnp.inf
# A target whose K-th and best left-out distinct candidate rank within this
# share of the row's largest |rank| is a near-tie: which of them a correct
# float32 program keeps is decided by rounding, so the reference's envelope
# spans both.
NEAR_TIE_RTOL = 1e-5


def dot(a, b):
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def dot_bf16x3(a, b):
    """a @ b from bfloat16 halves: hi*hi + hi*lo + lo*hi, summed in f32."""
    def split(x):
        hi = x.astype(jnp.bfloat16)
        return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)

    (ah, al), (bh, bl) = split(a), split(b)
    f = lambda x, y: jnp.dot(x, y, preferred_element_type=jnp.float32)
    return f(ah, bh) + f(ah, bl) + f(al, bh)


def leaky_relu(x, slope):
    return jnp.where(x >= 0, x, slope * x)


def select_top_k(rank, mask, src, k):
    """Per row, the ``k`` valid slots of largest ``rank``; on equal rank the
    lower slot wins. Returns ``(slots (T, k), kept (T, k) bool, near_tie
    (T,) bool, alt (T, k))``: ``alt`` is ``slots`` with a near-tie row's
    K-th slot swapped for its best rival, the selection a float32 program
    may make there instead. ``src`` (T, D) are the slots' source ids: a
    left-out slot with the same source as a kept one is no rival."""
    k = min(k, rank.shape[1])
    r = jnp.where(mask, rank, NEG_INF)
    top, slots = jax.lax.top_k(r, k)
    kept = jnp.take_along_axis(mask, slots, axis=1)
    left = mask.at[jnp.arange(r.shape[0])[:, None], slots].set(False)
    kept_src = jnp.take_along_axis(src, slots, axis=1)
    same = (src[:, :, None] == jnp.where(kept, kept_src, -1)[:, None, :]).any(-1)
    rivals = jnp.where(left & ~same, r, NEG_INF)
    rival = rivals.max(axis=1)
    scale = jnp.where(mask, jnp.abs(rank), 0.0).max(axis=1)
    near_tie = kept.all(axis=1) & (top[:, -1] - rival <= NEAR_TIE_RTOL * scale)
    alt = slots.at[:, -1].set(jnp.where(near_tie, jnp.argmax(rivals, axis=1), slots[:, -1]))
    return slots, kept, near_tie, alt


def attend(h_src, theta_src, theta_dst, nbr, mask, slots, kept, slope,
           theta_edge=None):
    """Softmax over the kept slots of LeakyReLU(θ_u* (+ edge term) + θ_*v)
    per head, then Σ α·h'_u. ``h_src`` (N, H, dh), ``theta_src`` (N, H),
    ``theta_dst`` (T, H), ``nbr``/``mask`` (T, D), ``theta_edge`` (T, D, H)
    or None. Returns (T, H, dh)."""
    u = jnp.take_along_axis(nbr, slots, axis=1)  # (T, K)
    th = theta_src[u]
    if theta_edge is not None:
        th = th + jnp.take_along_axis(theta_edge, slots[:, :, None], axis=1)
    e = leaky_relu(th + theta_dst[:, None, :], slope)
    e = jnp.where(kept[:, :, None], e, NEG_INF)
    e = e - jnp.max(jnp.where(kept[:, :, None], e, -3.0e38), axis=1, keepdims=True)
    w = jnp.where(kept[:, :, None], jnp.exp(e), 0.0)
    alpha = w / jnp.maximum(w.sum(axis=1, keepdims=True), 1e-30)
    return (alpha[:, :, :, None] * h_src[u]).sum(axis=1)
