"""Public wrapper for ADE pruned decode attention.

``impl``:
  * ``pallas`` — the kernel (Mosaic on TPU, interpret mode on CPU)
  * ``xla``    — lax.top_k formulation; partitions under SPMD, used by the
                 sharded serve_step and the dry-run.
"""
from __future__ import annotations


from repro.kernels.topk_decode_attention.kernel import topk_decode_attention_pallas
from repro.kernels.topk_decode_attention.ref import (
    full_decode_attention_ref,
    topk_decode_attention_ref,
)


def topk_decode_attention(
    q, k_cache, v_cache, lengths, prune_k=None, scale=None,
    impl: str = "xla",
):
    if prune_k is None:
        return full_decode_attention_ref(q, k_cache, v_cache, lengths, scale)
    if impl == "pallas":
        return topk_decode_attention_pallas(
            q, k_cache, v_cache, lengths, prune_k, scale
        )
    return topk_decode_attention_ref(q, k_cache, v_cache, lengths, prune_k, scale)
