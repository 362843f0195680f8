"""The work a forward needs, counted from the graph's real edges and K.

Counts come from the benchmark's own semantic graphs (``sgb_ref``), not
from the program's layouts, so they are the same whatever implements NA:
padding, tiles and grid steps add nothing. Bytes are float32/int32 (4
bytes an element).

* K1 (prune + softmax) per semantic graph: reads θ_u* (+ edge-type term)
  per head, the mask and the source id of every real edge, and θ_*v per
  target; writes α per head and the id of every kept slot. Operations: the
  head sum of the rank and one comparison per edge; add θ_*v, LeakyReLU,
  exp, sum and divide per kept slot and head.
* K2 (gather-aggregate): reads the kept h' rows (H·dh) and their α, writes
  one (H·dh) row per target; 2 operations per kept element.
* A forward's model FLOPs (``mfu``): the matrix products the model's
  equations need for the label type's logits, counted by the model's
  adapter (``dense_flops``), and the NA work above.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

F32 = 4


def na_costs(deg: np.ndarray, k: int, heads: int, head_dim: int) -> Dict[str, float]:
    """K1/K2 operations and bytes of one semantic graph with in-degrees
    ``deg`` (one entry per target) pruned to ``k``."""
    deg = np.asarray(deg, np.int64)
    t = deg.size
    e = int(deg.sum())
    kept = int(np.minimum(deg, k).sum())
    row = heads * head_dim
    return {
        "k1_ops": float(e * (heads + 1) + kept * heads * 5),
        "k1_bytes": float(F32 * (e * (heads + 2) + t * heads + kept * (heads + 1))),
        "k2_ops": float(2 * kept * row),
        "k2_bytes": float(F32 * (kept * (row + heads) + t * row)),
        "targets": float(t),
        "edges": float(e),
        "kept": float(kept),
    }


def forward_costs(adapter, g: dict, sgs: dict, cfg: dict) -> Dict[str, float]:
    """Per-forward totals: ``k1_ops``, ``k1_bytes``, ``k2_ops``,
    ``k2_bytes`` over every semantic graph (and layer), and ``flops``.
    What depends on the model comes from its adapter
    (``bench/models/<model>.py``): ``na_runs`` (how often a forward runs
    each semantic graph's NA) and ``dense_flops`` (its matrix products)."""
    heads, dh, k = cfg["heads"], cfg["head_dim"], cfg["prune_k"]
    runs = adapter.na_runs(cfg, g, sgs)
    tot = {"k1_ops": 0.0, "k1_bytes": 0.0, "k2_ops": 0.0, "k2_bytes": 0.0}
    for name, (_, _, m, _) in sgs.items():
        v = na_costs(m.sum(1), k, heads, dh)
        for key in tot:
            tot[key] += runs[name] * v[key]
    tot["flops"] = adapter.dense_flops(cfg, g, sgs) + tot["k1_ops"] + tot["k2_ops"]
    return tot


def roofline_share(ops: float, nbytes: float, seconds: float, peaks: dict):
    """Percent of the roofline: the least time the chip could take for
    ``ops`` and ``nbytes`` over the time taken; and which bound applies."""
    t_ops = ops / peaks["flops_per_s"]
    t_mem = nbytes / peaks["bytes_per_s"]
    bound = "bytes" if t_mem >= t_ops else "ops"
    return 100.0 * max(t_ops, t_mem) / seconds, bound
