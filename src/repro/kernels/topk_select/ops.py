"""Public wrapper: streaming top-K neighbor selection (the Pruner)."""
from __future__ import annotations


from repro.kernels.topk_select.kernel import topk_select_pallas
from repro.kernels.topk_select.ref import topk_select_ref


def topk_select(scores, mask, k, use_kernel: bool = True):
    """(T, D) scores + validity mask -> (values, slot ids) of top-k per row.

    ``use_kernel=False`` falls back to the XLA oracle (used inside jit paths
    that must partition under SPMD, where Pallas cannot run on this host).
    """
    if use_kernel:
        return topk_select_pallas(scores, mask, k)
    return topk_select_ref(scores, mask, k)
