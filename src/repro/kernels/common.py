"""Shared in-kernel helpers."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG = -3.0e38  # python float: below any real score, safe to capture in kernels


def interpret_mode() -> bool:
    """How every Pallas kernel of this package runs, decided at trace time.

    ``cpu`` runs the kernel bodies in Pallas interpret mode, ``tpu``
    compiles them with Mosaic. Any other backend is an error: no kernel
    here has a lowering for it, and silently interpreting on an
    accelerator would hide the device.
    """
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels run on 'tpu' (Mosaic) or 'cpu' (interpret mode); "
        f"the default JAX backend is {backend!r}"
    )


def argmin_onehot(rd: jax.Array):
    """Per-row one-hot of the FIRST minimum of ``rd`` (rows, K) plus the min.

    TPU-native argmin: masked-iota min instead of an argmin primitive. The
    one-hot is the vector analog of the heap-root pointer: replacing the
    minimum is a select against this mask, O(K/lanes) instead of O(log K)
    sequential compare-exchanges.
    """
    m = jnp.min(rd, axis=-1, keepdims=True)
    is_min = rd == m
    iota = jax.lax.broadcasted_iota(jnp.int32, rd.shape, rd.ndim - 1)
    first = jnp.min(jnp.where(is_min, iota, rd.shape[-1]), axis=-1, keepdims=True)
    return iota == first, m


def min_replace(rd_vals, rd_aux, cur_val, cur_aux):
    """One retention-domain step (Algorithm 1 lines 14-22), vectorized.

    rd_vals: (..., K); cur_val: (...,). Strict '>' keeps the incumbent on
    ties, matching the paper's 'discard when equal'. Returns updated
    (rd_vals, rd_aux) where rd_aux is a list of side arrays updated with the
    same one-hot mask (ids, per-head scores, ...). aux arrays may have extra
    trailing dims.
    """
    onehot, m = argmin_onehot(rd_vals)
    repl = onehot & (cur_val[..., None] > m)
    new_vals = jnp.where(repl, cur_val[..., None], rd_vals)
    new_aux = []
    for aux, cur in rd_aux:
        r = repl.reshape(repl.shape + (1,) * (aux.ndim - repl.ndim))
        c = cur[..., None, :] if aux.ndim > repl.ndim else cur[..., None]
        new_aux.append(jnp.where(r, c, aux))
    return new_vals, new_aux
