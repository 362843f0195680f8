"""Pallas TPU kernels for the performance-critical compute of ADE-HGNN.

Each package has ``kernel.py`` (pl.pallas_call + BlockSpec), ``ops.py``
(jit'd public wrapper) and ``ref.py`` (pure-jnp oracle). Kernels target TPU
(VMEM tiling, MXU-aligned blocks, scalar-prefetch DMA gather). One backend
decision, ``common.interpret_mode()`` at trace time, compiles them with
Mosaic on TPU and runs them in Pallas interpret mode on CPU.

  * ``topk_select``           — the Pruner: streaming retention domain
  * ``fused_prune_aggregate`` — ADE fused NA: prune + softmax + gather-aggregate
  * ``topk_decode_attention`` — ADE technique applied to LM decode (KV top-K)
"""
