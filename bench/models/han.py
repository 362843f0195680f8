"""HAN in the program: model registration and parameter shapes.

The program's ``HAN`` at the configuration's widths, over the metapath
graphs the configuration names. The benchmark makes the weights itself
(``run.init_params``) in the tree ``HAN.apply`` reads.
"""
from __future__ import annotations

SGB_KIND = "metapath"


def factory(cfg):
    from repro.core.models import HAN

    return lambda: HAN(heads=cfg["heads"], dh=cfg["head_dim"], num_layers=1)


def metapaths(cfg):
    return {k: tuple(v) for k, v in cfg["metapaths"].items()}


def na_runs(cfg, g, sgs):
    """Each metapath graph's NA runs once a forward."""
    return {name: 1 for name in sgs}


def dense_flops(cfg, g, sgs):
    """The matrix products HAN's equations need for the label type's
    logits: the endpoint type's projection, θ_u* and θ_*v per metapath,
    semantic attention, readout."""
    dim = cfg["heads"] * cfg["head_dim"]
    lt = g["label_type"]
    n, f, p = g["num_nodes"][lt], g["features"][lt].shape[1], len(sgs)
    flops = 2.0 * n * f * dim
    flops += p * 2.0 * 2 * n * dim
    flops += p * n * (2.0 * dim * cfg["semantic_attention_dim"] + 2 * dim)
    return flops + 2.0 * n * dim * g["num_classes"]


def param_shapes(cfg, g, sg_names):
    """``{path: shape}`` tree of HAN's parameters."""
    dim = cfg["heads"] * cfg["head_dim"]
    hid = cfg["semantic_attention_dim"]
    feat = {t: g["features"][t].shape[1] for t in g["node_types"]}
    return {
        "proj": {t: {"w": (f, dim), "b": (dim,)} for t, f in feat.items()},
        "attn": {
            mp: {"a_src": (cfg["heads"], cfg["head_dim"]),
                 "a_dst": (cfg["heads"], cfg["head_dim"])}
            for mp in sg_names
        },
        "sem": {"w": (dim, hid), "b": (hid,), "q": (hid,)},
        "out": {"w": (dim, g["num_classes"]), "b": (g["num_classes"],)},
    }
