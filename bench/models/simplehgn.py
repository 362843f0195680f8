"""Simple-HGN in the program: model registration and parameter shapes.

The program's ``SimpleHGN`` at the configuration's widths, over one union
graph per destination type. The benchmark makes the weights itself
(``run.init_params``) in the tree ``SimpleHGN.apply`` reads.
"""
from __future__ import annotations

SGB_KIND = "union"


def factory(cfg):
    from repro.core.models import SimpleHGN

    return lambda: SimpleHGN(
        heads=cfg["heads"], dh=cfg["head_dim"], num_layers=cfg["num_layers"],
        rel_dim=cfg["edge_type_dim"],
    )


def metapaths(cfg):
    return None


def na_runs(cfg, g, sgs):
    """Every union graph's NA runs in each layer but the last, which needs
    the label type's only (the compiled program drops the rest too)."""
    lt, layers = g["label_type"], cfg["num_layers"]
    return {name: layers - 1 + (dst == lt) for name, (dst, _, _, _) in sgs.items()}


def dense_flops(cfg, g, sgs):
    """Per layer, every type's projection and θ_u*, and its residual and
    θ_*v where the layer's output is read; the readout."""
    dim = cfg["heads"] * cfg["head_dim"]
    lt, layers = g["label_type"], cfg["num_layers"]
    n = g["num_nodes"]
    flops = 0.0
    for layer in range(layers):
        last = layer == layers - 1
        for t in g["node_types"]:
            fin = g["features"][t].shape[1] if layer == 0 else dim
            flops += 2.0 * n[t] * fin * dim + 2.0 * n[t] * dim  # projection, θ_u*
            if not last or t == lt:
                flops += 2.0 * n[t] * fin * dim + 2.0 * n[t] * dim  # residual, θ_*v
    return flops + 2.0 * n[lt] * dim * g["num_classes"]


def param_shapes(cfg, g, sg_names):
    h, dh, dr = cfg["heads"], cfg["head_dim"], cfg["edge_type_dim"]
    dim = h * dh
    n_etypes = len(g["relations"]) + 1  # + the self-loop type
    layers = []
    for layer in range(cfg["num_layers"]):
        feat = {
            t: (g["features"][t].shape[1] if layer == 0 else dim)
            for t in g["node_types"]
        }
        layers.append({
            "proj": {t: {"w": (f, dim), "b": (dim,)} for t, f in feat.items()},
            "a_src": (h, dh),
            "a_dst": (h, dh),
            "a_rel": (h, dr),
            "rel_emb": (n_etypes, h * dr),
            "res": {t: (f, dim) for t, f in feat.items()},
        })
    return {
        "layers": layers,
        "out": {"w": (dim, g["num_classes"]), "b": (g["num_classes"],)},
    }
