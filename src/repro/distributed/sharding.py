"""Logical-axis sharding rules (MaxText-style), resolved against the ambient
mesh at trace time.

Layers annotate activations with *logical* axis names via ``constrain``;
parameters get PartitionSpecs from name-pattern rules via
``param_sharding_tree``. Rules resolve to whatever mesh is in context
(``jax.set_mesh``): the single-pod ``("data","model")`` mesh, the multi-pod
``("pod","data","model")`` mesh, or no mesh at all (tests/benches — no-op).
An axis is silently dropped when the dim size does not divide the mesh axis
(e.g. 8 kv heads on a 16-way model axis) — XLA would pad, we prefer
replication there and shard a different dim instead.

Graph workloads add three logical axes (``bucket_tiles``, ``targets``,
``ntype_feat``, see DEFAULT_RULES) and the mesh helpers
(``ambient_mesh``/``graph_mesh``/``shard_map_call``/``replicate``) that the
sharded grouped-NA inference path in ``repro.core.flows`` binds to: when a
mesh with a ``bucket_tiles`` rule axis is ambient, bucketed NA shard_maps
over it; with no mesh every helper degrades to a no-op and the single-
device path runs unchanged.
"""
from __future__ import annotations

import contextlib
import re
from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

# logical axis -> preferred mesh axes (in order; tuple = shard over several)
DEFAULT_RULES: Dict[str, Tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "seq": (),
    "embed": (),
    "heads": ("model",),
    "kv_heads": ("model",),
    "head_dim": (),
    "ffn": ("model",),
    "experts": ("model",),
    "vocab": ("model",),
    "moe_group": ("pod", "data"),
    "cache_seq": ("model",),  # decode KV cache: flash-decode seq sharding
    "act_seq": ("model",),  # Megatron-SP residual-stream seq sharding
    "ctx_seq": (),  # encoder/image context length
    "fsdp": ("data",),  # ZeRO-3 param sharding (joined by pod when present)
    "lru": ("model",),
    # --- HGNN graph axes (ADE semantic-graph NA) ------------------------
    # bucket_tiles: the shard-stacked axis of a ShardedBucketLayout's
    # grouped tile stack — the axis grouped NA shard_maps over. Its rule
    # names the mesh axis the sharded inference path binds to.
    "bucket_tiles": ("data",),
    # targets: the target-vertex axis of NA outputs / logits. Replicated by
    # default: cross-target reductions (semantic fusion's mean) must see
    # identical operand order on every device for bit-exact parity with the
    # single-device flow. Opt into ("data",) via axis_rules for consumers
    # that want target-sharded outputs and can live with resharded math.
    "targets": (),
    # ntype_feat: per-node-type feature/activation tables. Replicated — NA
    # gathers arbitrary global source ids, so every shard needs the full
    # table (the paper's semantic graphs share one global vertex table).
    "ntype_feat": (),
}

_RULES = dict(DEFAULT_RULES)


@contextlib.contextmanager
def axis_rules(overrides: Dict[str, Tuple[str, ...]]):
    """Temporarily override logical->physical rules (used by §Perf passes)."""
    global _RULES
    old = dict(_RULES)
    _RULES.update(overrides)
    try:
        yield
    finally:
        _RULES = old


def _axes_of(m) -> Dict[str, int]:
    if m is None or m.empty:
        return {}
    return dict(zip(m.axis_names, m.axis_sizes))


def _mesh_axes() -> Dict[str, int]:
    # the ambient mesh, set by ``jax.set_mesh``; abstract inside a trace
    return _axes_of(jax.sharding.get_abstract_mesh())


def ambient_mesh():
    """The mesh given to ``jax.set_mesh``, or ``None`` without one.

    Outside a trace this is the concrete ``jax.sharding.Mesh``, whose
    devices an eager ``device_put`` needs. Inside a trace JAX exposes only
    the abstract mesh, which ``shard_map`` and ``NamedSharding`` accept
    just the same.
    """
    abstract = jax.sharding.get_abstract_mesh()
    if abstract.empty:
        return None
    try:
        mesh = jax.sharding.get_mesh()
    except ValueError:  # inside a trace
        return abstract
    return abstract if mesh.empty else mesh


def graph_shard_axis(mesh=None) -> Optional[str]:
    """The mesh axis grouped NA shards over: the first ``bucket_tiles``
    rule axis present in ``mesh`` (ambient mesh when omitted)."""
    axes = _axes_of(mesh) if mesh is not None else _mesh_axes()
    for ax in _RULES.get("bucket_tiles", ()):
        if ax in axes:
            return ax
    return None


def graph_mesh():
    """``(mesh, axis_name, n_shards)`` for sharded grouped NA, or ``None``
    when no mesh with a ``bucket_tiles`` rule axis is ambient —
    the no-mesh no-op contract of the transparent sharding path."""
    mesh = ambient_mesh()
    if mesh is None:
        return None
    ax = graph_shard_axis(mesh)
    if ax is None:
        return None
    return mesh, ax, _axes_of(mesh)[ax]


def shard_map_call(body, mesh, in_specs, out_specs):
    """Wrap ``body`` in ``jax.shard_map`` with replication checking off (the
    pallas calls inside the NA body don't carry replication info)."""
    return jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def replicate(x: jax.Array, mesh) -> jax.Array:
    """Force ``x`` fully replicated over ``mesh`` — the sharded NA path's
    single all-gather. ``with_sharding_constraint`` under a trace,
    ``device_put`` (an actual resharding transfer) when eager."""
    s = NamedSharding(mesh, P())
    if isinstance(x, jax.core.Tracer):
        return jax.lax.with_sharding_constraint(x, s)
    return jax.device_put(x, s)


def resolve_spec(
    names: Sequence[Optional[str]], shape: Sequence[int]
) -> Optional[P]:
    """Logical names per dim -> PartitionSpec against the ambient mesh."""
    mesh = _mesh_axes()
    if not mesh:
        return None
    spec = []
    used = set()
    for name, dim in zip(names, shape):
        axes = []
        size = 1
        for ax in _RULES.get(name, ()) if name else ():
            if ax in mesh and ax not in used and dim % (size * mesh[ax]) == 0:
                axes.append(ax)
                size *= mesh[ax]
        used.update(axes)
        spec.append(tuple(axes) if len(axes) > 1 else (axes[0] if axes else None))
    return P(*spec)


def constrain(x: jax.Array, *names: Optional[str]) -> jax.Array:
    """with_sharding_constraint by logical names; no-op without a mesh."""
    spec = resolve_spec(names, x.shape)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# Parameter sharding: name-pattern -> logical axes per dim.
# Patterns are matched against the '/'-joined pytree path, first match wins.
# `F` marks dims additionally sharded over the fsdp axes when cfg.fsdp.
# ---------------------------------------------------------------------------

_PARAM_PATTERNS: Tuple[Tuple[str, Tuple[Optional[str], ...]], ...] = (
    (r"embed/table", ("vocab", "F")),
    (r"lm_head/w", ("F", "vocab")),
    (r"(attn|cross).*/w[qkv]$", ("F", "heads")),
    (r"(attn|cross).*/wo$", ("heads", "F")),
    (r"(attn|cross).*/b[qkv]$", ("heads",)),
    (r"moe/router/w", (None, "experts")),
    (r"moe/experts/w(i|g)$", ("experts", "F", "ffn")),
    (r"moe/experts/wo$", ("experts", "ffn", "F")),
    (r"mlp/w(i|g)$", ("F", "ffn")),
    (r"mlp/wo$", ("ffn", "F")),
    (r"lru/(wx|wgate)$", ("F", "lru")),
    (r"lru/w_out$", ("lru", "F")),
    (r"lru/(wa|wi)$", (None, "lru")),
    (r"lru/conv_w", (None, "lru")),
    (r"lru/(lam|ba|bi|conv_b)$", ("lru",)),
    (r"rwkv/w[rkvg]$", ("F", "heads")),
    (r"rwkv/wo$", ("heads", "F")),
    (r"rwkv/(wk2)$", ("F", "ffn")),
    (r"rwkv/(wv2)$", ("ffn", "F")),
    (r"rwkv/(wr2)$", ("F", None)),
    (r"rwkv/decay_a$", ("F", None)),
    (r"rwkv/decay_b$", (None, "heads")),
    (r"rwkv/u$", ("heads", None)),
)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_logical_axes(path_str: str, ndim: int, fsdp: bool):
    for pat, axes in _PARAM_PATTERNS:
        if re.search(pat, path_str):
            # stacked layer params have a leading layer dim; right-align
            pad = ndim - len(axes)
            full = (None,) * pad + tuple(axes)
            return tuple(
                ("fsdp" if fsdp else None) if a == "F" else a for a in full
            )
    return (None,) * ndim


def param_sharding_tree(params_shape, mesh, fsdp: bool = False):
    """ShapeDtypeStruct tree -> NamedSharding tree (for jit in_shardings)."""
    rules = dict(_RULES)
    if "pod" in mesh.axis_names:
        rules["fsdp"] = ("pod", "data")
        rules["batch"] = ("pod", "data")
        rules["moe_group"] = ("pod", "data")

    def one(path, leaf):
        names = param_logical_axes(_path_str(path), len(leaf.shape), fsdp)
        spec = []
        used = set()
        msizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
        for name, dim in zip(names, leaf.shape):
            axes, size = [], 1
            for ax in rules.get(name, ()) if name else ():
                if ax in msizes and ax not in used and dim % (size * msizes[ax]) == 0:
                    axes.append(ax)
                    size *= msizes[ax]
            used.update(axes)
            spec.append(tuple(axes) if len(axes) > 1 else (axes[0] if axes else None))
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(one, params_shape)
