"""Plain float32 Simple-HGN forward (Lv et al., KDD'21), the reference
for ``simplehgn-*`` configurations, as the program defines the model.
Imports nothing of the program.

Per layer: h' = X_t W_t + b_t for every type; per destination type, over
its union graph (each relation's in-edges plus a self-loop type), the
target keeps its K neighbours of largest Σ_heads (θ_u* + θ_ψ(e)) with
θ_u* = a_src·h'_u, θ_ψ = a_rel·r_ψ (r = the edge-type embedding), α =
softmax of LeakyReLU(θ_u* + θ_ψ(e) + a_dst·h'_v), and the new activation is
ELU(Σ α h'_u + X_t R_t). Logits = h_label W_out + b_out. The program has no
attention residual and no L2-normalised output, and neither has this.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import sgb_ref
from bench.reference.common import attend, dot, select_top_k

SLOPE = 0.2


def semantic_graphs(g, cfg):
    return sgb_ref.union_graphs(g, cfg["max_degree"], cfg["graph"]["data_seed"])


def forward(g, sgs, params, cfg, dot=dot):
    """``(lo, hi, left_out)`` for every target of the label type, as numpy:
    the envelope of the logits over what a correct program may keep at the
    near-ties, and the rows that envelope cannot bound.

    A near-tie row of a layer before the last is a source of uncertainty:
    a correct program keeps its K-th neighbour or its rival. The reference
    runs two variants through the layers, A (the reference's own
    selection) and B (every near-tie resolved the other way), and counts
    per row the distinct uncertain sources among its inputs (itself and
    its distinct neighbours). A last-layer row with at most one is either
    A's or B's, and the envelope spans both, with the last layer's own
    near-ties resolved both ways in each; a row with two or more is left
    out (a mix of A and B)."""
    types = tuple(g["node_types"])
    x = {t: jnp.asarray(g["features"][t]) for t in types}
    graphs = {t: tuple(jnp.asarray(a) for a in sgs[f"union:{t}"][1:]) for t in types}
    lo, hi, left_out = _forward(params, x, graphs, types, g["label_type"], cfg["heads"],
                                cfg["head_dim"], cfg["edge_type_dim"], cfg["prune_k"], dot)
    return np.asarray(lo), np.asarray(hi), np.asarray(left_out)


def _distinct_inputs(nbr, msk, self_id, count):
    """Per row, the sum of ``count`` over the row's distinct neighbours
    other than itself, plus its own."""
    key = jnp.sort(jnp.where(msk & (nbr != self_id[:, None]), nbr, -1), axis=1)
    first = (key >= 0) & jnp.concatenate(
        [jnp.ones_like(key[:, :1], bool), key[:, 1:] != key[:, :-1]], axis=1)
    return count[self_id] + jnp.where(first, count[jnp.maximum(key, 0)], 0).sum(axis=1)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8, 9))
def _forward(params, x, graphs, types, label_type, heads, dh, dr, k, dot):
    n = {t: x[t].shape[0] for t in types}
    offs = dict(zip(types, np.cumsum([0] + [n[t] for t in types])[:-1].tolist()))
    self_id = {t: offs[t] + jnp.arange(n[t]) for t in types}
    last = len(params["layers"]) - 1

    def layer(lp, x, flip, out_types):
        """One layer of one variant: per type in ``out_types``, the output
        at its selection (``flip``: near-ties resolved the other way) and
        at the other one, and its near-tie rows."""
        h = {t: (dot(x[t], lp["proj"][t]["w"]) + lp["proj"][t]["b"]).reshape(-1, heads, dh)
             for t in types}
        hg = jnp.concatenate([h[t] for t in types])
        th_src = (hg * lp["a_src"]).sum(-1)
        th_rel = (lp["rel_emb"].reshape(-1, heads, dr) * lp["a_rel"]).sum(-1)
        out = {}
        for t in out_types:
            nbr, msk, ety = graphs[t]
            th_edge = th_rel[ety]  # (T, D, H)
            slots, kept, tie, alt = select_top_k((th_src[nbr] + th_edge).sum(-1), msk, nbr, k)
            th_dst = (h[t] * lp["a_dst"]).sum(-1)
            res = dot(x[t], lp["res"][t])
            ys = [jax.nn.elu(attend(hg, th_src, th_dst, nbr, msk, sl, kept, SLOPE, th_edge)
                             .reshape(n[t], heads * dh) + res)
                  for sl in (slots, alt)]
            out[t] = (jnp.where(flip, ys[1], ys[0]), jnp.stack(ys), tie)
        return out

    flips = jnp.asarray([False, True])
    xs = {t: jnp.stack([x[t], x[t]]) for t in types}
    count = {t: jnp.zeros(n[t], jnp.int32) for t in types}  # uncertain sources, capped at 2
    for i, lp in enumerate(params["layers"]):
        out_types = (label_type,) if i == last else types
        out = jax.vmap(lambda xv, f: layer(lp, xv, f, out_types))(xs, flips)
        cg = jnp.concatenate([count[t] for t in types])
        inputs = {t: jnp.minimum(2, _distinct_inputs(graphs[t][0], graphs[t][1], self_id[t], cg))
                  for t in out_types}
        if i == last:
            break
        xs = {t: out[t][0] for t in types}
        count = {t: jnp.minimum(2, inputs[t] + (out[t][2].any(axis=0))) for t in types}
    w, b = params["out"]["w"], params["out"]["b"]
    both = out[label_type][1]  # (variant, selection, T, dim)
    logits = dot(both.reshape(-1, both.shape[-1]), w).reshape(*both.shape[:-1], -1) + b
    return logits.min((0, 1)), logits.max((0, 1)), inputs[label_type] >= 2
