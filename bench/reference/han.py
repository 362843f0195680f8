"""Plain float32 HAN forward (Wang et al., WWW'19), the reference for
``han-*`` configurations. Imports nothing of the program.

Per metapath graph: θ_u* = a_src·h'_u and θ_*v = a_dst·h'_v per head
(h' = X W + b of the endpoint type), each target keeps its K neighbours of
largest Σ_heads θ_u*, α = softmax over them of LeakyReLU(θ_u* + θ_*v), and
z = ELU(Σ α h'_u). Semantic attention: w_p = mean_v qᵀ tanh(W z_p,v + b),
β = softmax(w), z = Σ β_p z_p; logits = z W_out + b_out.

At a near-tie row a correct float32 program may keep the rival of the K-th
neighbour instead, so the reference returns an envelope of the logits
rather than one value. The row's own z_p lies between the two selections'
(elementwise), and the logits are bounded from that box through the
linear readout. β couples every row: w_p moves by the row's share of the
mean, so the envelope also spans every corner of the box in which w lies
whatever was kept at the near-ties (per metapath, from the sum of the
rows' negative moves to the sum of their positive ones); the logits are
monotone in each w_p over so small a box.
"""
from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np

from bench import sgb_ref
from bench.reference.common import attend, dot, select_top_k

SLOPE = 0.2


def semantic_graphs(g, cfg):
    return sgb_ref.metapath_graphs(
        g, cfg["metapaths"], cfg["max_degree"], cfg["metapath_fanout_cap"],
        cfg["graph"]["data_seed"],
    )


def forward(g, sgs, params, cfg, dot=dot):
    """``(lo, hi, left_out)`` for every target of the label type, as numpy:
    the envelope of the logits over what a correct program may keep at the
    near-tie rows; no row is left out."""
    t = g["label_type"]
    off = sgb_ref.type_offsets(g)[t]
    graphs = {name: (jnp.asarray(nbr - off), jnp.asarray(msk))
              for name, (dst_t, nbr, msk, _) in sgs.items() if dst_t == t}
    assert len(graphs) == len(sgs), "every metapath must end at the label type"
    lo, hi = _forward(params, jnp.asarray(g["features"][t]), graphs, t,
                      cfg["heads"], cfg["head_dim"], cfg["prune_k"], dot)
    return np.asarray(lo), np.asarray(hi), np.zeros(lo.shape[0], bool)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _forward(params, x, graphs, t, heads, dh, k, dot):
    p = params["proj"][t]
    h = (dot(x, p["w"]) + p["b"]).reshape(-1, heads, dh)
    s = params["sem"]
    score = lambda z: (jnp.tanh(dot(z, s["w"]) + s["b"]) * s["q"]).sum(-1)
    z_lo, z_hi, ws, moves = [], [], [], []
    for name, (nbr, msk) in graphs.items():
        a = params["attn"][name]
        th_src = (h * a["a_src"]).sum(-1)
        th_dst = (h * a["a_dst"]).sum(-1)
        slots, kept, _, alt = select_top_k(th_src[nbr].sum(-1), msk, nbr, k)
        z, z_alt = (jax.nn.elu(attend(h, th_src, th_dst, nbr, msk, sl, kept, SLOPE)
                               .reshape(-1, heads * dh)) for sl in (slots, alt))
        e = score(z)
        z_lo.append(jnp.minimum(z, z_alt))
        z_hi.append(jnp.maximum(z, z_alt))
        ws.append(e.mean())
        d = (score(z_alt) - e) / e.shape[0]
        moves.append((jnp.minimum(d, 0).sum(), jnp.maximum(d, 0).sum()))
    z_lo, z_hi, w = jnp.stack(z_lo), jnp.stack(z_hi), jnp.stack(ws)
    corners = jnp.stack([jnp.stack(c) for c in itertools.product(*moves)])  # (2^P, P)
    beta = jax.nn.softmax(w + corners, axis=-1)
    w_out, b_out = params["out"]["w"], params["out"]["b"]
    lo, hi = [], []
    for bc in beta:  # β > 0: the fused box is Σ β_p [z_lo, z_hi]
        f_lo = (bc[:, None, None] * z_lo).sum(0)
        f_hi = (bc[:, None, None] * z_hi).sum(0)
        mid = dot((f_lo + f_hi) / 2, w_out) + b_out
        rad = dot((f_hi - f_lo) / 2, jnp.abs(w_out))
        lo.append(mid - rad)
        hi.append(mid + rad)
    return jnp.stack(lo).min(0), jnp.stack(hi).max(0)
