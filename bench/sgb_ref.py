"""The reference's own semantic-graph build, independent of the program.

The program caps a semantic graph's in-degree at ``max_degree`` and a
metapath join's fan-out per intermediate vertex at ``fanout_cap``, drawing
the survivors at random from the data seed. The reference must aggregate
over the same neighbours, so this module repeats that build from the raw
edge lists: the same joins, the same de-duplication, the same slot order
(arrival order; over-cap rows re-ranked at random) and the same random
draws in the same order from ``np.random.default_rng(seed)``. It is a copy
of the semantics, not an import: the program's tables are never read.

Each builder returns ``{name: (dst_type, nbr, mask, etype)}`` with ``nbr``
an ``(T, D)`` table of GLOBAL source ids in slot order.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import numpy as np


def type_offsets(g: dict) -> Dict[str, int]:
    off, out = 0, {}
    for t in g["node_types"]:
        out[t] = off
        off += g["num_nodes"][t]
    return out


def pad_rows(src, dst, n_targets, max_degree, rng, etype=None):
    """Edges -> padded rows in arrival order; rows over ``max_degree`` keep
    a uniform random ``max_degree`` of their edges (one ``rng.random``
    draw over the slots of all such rows, in edge order)."""
    e = len(dst)
    counts = np.bincount(dst, minlength=n_targets) if e else np.zeros(n_targets, np.int64)
    cap = int(counts.max()) if e and counts.max() > 0 else 1
    if max_degree is not None:
        cap = min(cap, max_degree)
    cap = max(cap, 1)
    nbr = np.zeros((n_targets, cap), np.int32)
    msk = np.zeros((n_targets, cap), bool)
    ety = np.zeros((n_targets, cap), np.int32)
    if e == 0:
        return nbr, msk, ety
    order = np.argsort(dst * e + np.arange(e, dtype=np.int64))  # stable by dst
    src = src[order]
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    pos = np.arange(e, dtype=np.int64) - np.repeat(starts, counts)
    over = counts > cap
    if over.any():
        sub = np.flatnonzero(np.repeat(over, counts))
        row = np.searchsorted(np.cumsum(counts), sub, side="right")
        order_sub = np.lexsort((rng.random(sub.size), row))
        srt, row = sub[order_sub], row[order_sub]
        idx = np.arange(srt.size, dtype=np.int64)
        first = np.empty(srt.size, bool)
        first[0] = True
        np.not_equal(row[1:], row[:-1], out=first[1:])
        pos[srt] = idx - np.maximum.accumulate(np.where(first, idx, 0))
    keep = pos < cap
    flat = np.repeat(np.arange(n_targets, dtype=np.int64) * cap,
                     np.minimum(counts, cap)) + pos[keep]
    nbr.reshape(-1)[flat] = src[keep]
    msk.reshape(-1)[flat] = True
    if etype is not None:
        ety.reshape(-1)[flat] = etype[order][keep]
    return nbr, msk, ety


def join(ab, bc, fanout_cap, rng):
    """A->B join B->C on B. Per B, its pairs in row-major order; a B with
    more than ``fanout_cap`` pairs instead draws that many uniformly, with
    replacement (one draw each for the left and the right side)."""
    a, b1 = ab
    b2, c = bc
    o1 = np.argsort(b1, kind="stable")
    a, b1 = a[o1], b1[o1]
    o2 = np.argsort(b2, kind="stable")
    b2, c = b2[o2], c[o2]
    n_b = int(max(b1.max(initial=-1), b2.max(initial=-1))) + 1
    c1 = np.bincount(b1, minlength=n_b).astype(np.int64)
    c2 = np.bincount(b2, minlength=n_b).astype(np.int64)
    s1 = np.concatenate([[0], np.cumsum(c1)[:-1]])
    s2 = np.concatenate([[0], np.cumsum(c2)[:-1]])
    pairs = c1 * c2
    take = np.minimum(pairs, fanout_cap)
    total = int(take.sum())
    if total == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    b_of = np.repeat(np.arange(n_b, dtype=np.int64), take)
    p = np.arange(total, dtype=np.int64) - np.concatenate([[0], np.cumsum(take)[:-1]])[b_of]
    c2b = np.maximum(c2[b_of], 1)
    li, ri = p // c2b, p % c2b
    capped = np.flatnonzero(pairs[b_of] > fanout_cap)
    if capped.size:
        li[capped] = rng.integers(0, c1[b_of[capped]])
        ri[capped] = rng.integers(0, c2[b_of[capped]])
    return a[s1[b_of] + li], c[s2[b_of] + ri]


def _pairs(g: dict, name: str) -> Tuple[np.ndarray, np.ndarray, str, str]:
    rev = name.endswith("_rev")
    base = name[:-4] if rev else name
    src_t, _, dst_t = next(r for r in g["relations"] if r[1] == base)
    s, d = (a.astype(np.int64) for a in g["edges"][base])
    return (d, s, dst_t, src_t) if rev else (s, d, src_t, dst_t)


def metapath_graphs(g: dict, metapaths: Dict[str, Sequence[str]], max_degree,
                    fanout_cap: int, seed: int):
    """HAN's semantic graphs: each metapath composed, made simple, with a
    self-loop per target appended after the composed edges."""
    rng = np.random.default_rng(seed)
    offs = type_offsets(g)
    out = {}
    for name, chain in metapaths.items():
        s, d, _, dst_t = _pairs(g, chain[0])
        for rel in chain[1:]:
            s2, d2, _, dst_t = _pairs(g, rel)
            s, d = join((s, d), (s2, d2), fanout_cap, rng)
        n = g["num_nodes"][dst_t]
        _, uniq = np.unique(s * (n + 1) + d, return_index=True)
        loops = np.arange(n, dtype=np.int64)
        s = np.concatenate([s[uniq], loops])
        d = np.concatenate([d[uniq], loops])
        nbr, msk, ety = pad_rows(s + offs[dst_t], d, n, max_degree, rng)
        out[name] = (dst_t, nbr, msk, ety)
    return out


def union_graphs(g: dict, max_degree, seed: int):
    """Simple-HGN's semantic graphs: per destination type, the in-edges of
    every relation (edge type = relation index) and then a self-loop per
    target (edge type = number of relations)."""
    rng = np.random.default_rng(seed)
    offs = type_offsets(g)
    loop_id = len(g["relations"])
    out = {}
    for dst_t in g["node_types"]:
        srcs, dsts, ets = [], [], []
        for i, (src_t, name, d_t) in enumerate(g["relations"]):
            if d_t != dst_t:
                continue
            s, d = g["edges"][name]
            srcs.append(s.astype(np.int64) + offs[src_t])
            dsts.append(d.astype(np.int64))
            ets.append(np.full(len(s), i, np.int64))
        n = g["num_nodes"][dst_t]
        loops = np.arange(n, dtype=np.int64)
        srcs.append(loops + offs[dst_t])
        dsts.append(loops)
        ets.append(np.full(n, loop_id, np.int64))
        nbr, msk, ety = pad_rows(np.concatenate(srcs), np.concatenate(dsts), n,
                                 max_degree, rng, np.concatenate(ets))
        out[f"union:{dst_t}"] = (dst_t, nbr, msk, ety)
    return out
