"""The benchmark's own heterogeneous-graph generator.

A copy of the program's synthetic generator (``_power_law_degrees`` /
``_bipartite_edges`` / ``make_hetg``), changed in three ways so that the
graph has a published dataset's counts:

* a relation may draw its degrees per SOURCE instead of per destination,
  where the published relation fixes them there (every DBLP paper has
  exactly one venue);
* the degree sequence is adjusted to sum to the published edge count, and
  edges lost to de-duplication are drawn again, so the edge count is exact;
* a node type may carry a one-hot id feature instead of Gaussian features.

The graph is a plain dict of numpy arrays and imports nothing of the
program: the plain reference reads it as it is, and the harness converts
it to the program's ``HetGraph``. Everything is drawn from the data seed
in the configuration file, so a configuration always has the same graph.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

REDRAW_ROUNDS = 64  # rounds of re-drawing duplicate edges before giving up
NOISE_EDGES = 0.15  # share of each relation's edges drawn outside the community


def power_law_degrees(rng, n: int, mean_deg: float, alpha: float = 2.1):
    """Heavy-tailed integer degrees (>= 1) with the requested mean."""
    raw = rng.pareto(alpha, size=n) + 1.0
    raw = raw / raw.mean() * mean_deg
    return np.maximum(1, np.round(raw)).astype(np.int64)


def exact_total(rng, deg: np.ndarray, total: int, cap: int) -> np.ndarray:
    """Adjust ``deg`` (each in [1, cap]) to sum to ``total``: add or remove
    one unit at nodes drawn uniformly, a vectorised round at a time."""
    deg = np.minimum(deg, cap).copy()
    if not deg.size <= total <= deg.size * cap:
        raise ValueError(f"{total} edges do not fit {deg.size} nodes of cap {cap}")
    while True:
        diff = total - int(deg.sum())
        if diff == 0:
            return deg
        room = np.flatnonzero(deg < cap) if diff > 0 else np.flatnonzero(deg > 1)
        pick = rng.choice(room, size=min(abs(diff), room.size), replace=False)
        deg[pick] += 1 if diff > 0 else -1


def _picks(rng, n_other, comm_other, comm_of_edge, noise):
    """One endpoint per edge: mostly from the edge's community pool on the
    other side, uniform for the ``noise`` share and for empty pools."""
    n_comm = int(max(comm_other.max(), comm_of_edge.max())) + 1
    total = comm_of_edge.size
    same = rng.random(total) >= noise
    rand = rng.integers(0, n_other, size=total)
    pool = np.argsort(comm_other, kind="stable")
    sizes_c = np.bincount(comm_other, minlength=n_comm)
    starts_c = np.concatenate([[0], np.cumsum(sizes_c)[:-1]])
    sizes = sizes_c[comm_of_edge]
    offs = rng.integers(0, np.maximum(sizes, 1), size=total)
    same_pick = pool[np.minimum(starts_c[comm_of_edge] + offs, n_other - 1)]
    return np.where(same & (sizes > 0), same_pick, rand)


def relation_edges(
    rng, n_src: int, n_dst: int, comm_src, comm_dst, num_edges: int,
    per: str, degree: int | None,
) -> Tuple[np.ndarray, np.ndarray, int]:
    """``num_edges`` distinct src->dst edges. ``per`` names the side whose
    degrees are drawn (``"src"`` or ``"dst"``): a fixed ``degree`` where the
    published relation fixes it, else a power law with the published mean.
    A drawn degree is capped at the other side's mean community size.
    Returns ``(src, dst, shortfall)``; the shortfall is 0 unless duplicates
    could not be re-drawn away."""
    n_side, n_other = (n_src, n_dst) if per == "src" else (n_dst, n_src)
    comm_side, comm_other = (comm_src, comm_dst) if per == "src" else (comm_dst, comm_src)
    if degree is not None:
        deg = np.full(n_side, int(degree), np.int64)
        if int(deg.sum()) != num_edges:
            raise ValueError(f"degree {degree} x {n_side} nodes != {num_edges} edges")
    else:
        # a node's degree is capped at the mean community pool on the other
        # side, so that its edges can be distinct
        cap = max(1, n_other // (int(comm_other.max()) + 1))
        deg = power_law_degrees(rng, n_side, num_edges / n_side)
        deg = exact_total(rng, deg, num_edges, cap)
    side = np.repeat(np.arange(n_side, dtype=np.int64), deg)
    other = _picks(rng, n_other, comm_other, comm_side[side], NOISE_EDGES)
    for _ in range(REDRAW_ROUNDS):
        key = side * n_other + other
        _, first = np.unique(key, return_index=True)
        dup = np.ones(key.size, bool)
        dup[first] = False
        if not dup.any():
            break
        other[dup] = _picks(rng, n_other, comm_other, comm_side[side[dup]], NOISE_EDGES)
    key = side * n_other + other
    _, first = np.unique(key, return_index=True)
    side, other = side[first], other[first]
    src, dst = (side, other) if per == "src" else (other, side)
    return src.astype(np.int64), dst.astype(np.int64), num_edges - int(first.size)


def make_graph(spec: dict, scale: float = 1.0) -> dict:
    """The configuration's ``graph`` section -> a plain graph dict.

    ``scale`` shrinks node and edge counts for a CPU rehearsal; the
    benchmark's cells always run at ``scale=1.0``. Keys of the result:
    ``node_types``, ``num_nodes``, ``features``, ``relations``
    (``(src_type, name, dst_type)``), ``edges`` (name -> ``(src, dst)``),
    ``label_type``, ``labels``, ``num_classes`` and ``shortfall`` (edges
    per relation that de-duplication left undrawn).
    """
    rng = np.random.default_rng(int(spec["data_seed"]))
    classes = int(spec["num_classes"])
    s = lambda n: max(8, int(round(n * scale)))
    nodes = {t: s(v["count"]) for t, v in spec["nodes"].items()}
    comm = {t: rng.integers(0, classes, size=n) for t, n in nodes.items()}
    feats: Dict[str, np.ndarray] = {}
    for t, v in spec["nodes"].items():
        n = nodes[t]
        if v.get("features") == "one_hot":
            feats[t] = np.eye(n, dtype=np.float32)
            continue
        f = int(v["features"])
        centroids = rng.standard_normal((classes, f), dtype=np.float32)
        x = rng.standard_normal((n, f), dtype=np.float32)  # unit noise
        x += centroids[comm[t]]
        feats[t] = x
    relations, edges, shortfall = [], {}, {}
    for r in spec["relations"]:
        src_t, name, dst_t = r["src"], r["name"], r["dst"]
        e = r["edges"]
        if scale != 1.0:  # a rehearsal: as many edges as still fit
            side, other = (src_t, dst_t) if r["per"] == "src" else (dst_t, src_t)
            e = min(max(nodes[side], s(e)), nodes[side] * max(1, nodes[other] // classes))
        if r.get("degree") is not None:  # a fixed degree fixes the count too
            e = int(r["degree"]) * nodes[src_t if r["per"] == "src" else dst_t]
        src, dst, short = relation_edges(
            rng, nodes[src_t], nodes[dst_t], comm[src_t], comm[dst_t], e,
            r["per"], r.get("degree"),
        )
        relations.append((src_t, name, dst_t))
        edges[name] = (src, dst)
        shortfall[name] = short
    for name, base in spec.get("reverse_relations", {}).items():
        src_t, _, dst_t = next(r for r in relations if r[1] == base)
        s_, d_ = edges[base]
        relations.append((dst_t, name, src_t))
        edges[name] = (d_.copy(), s_.copy())
    label_t = spec["label_type"]
    return {
        "node_types": tuple(nodes),
        "num_nodes": nodes,
        "features": feats,
        "relations": tuple(relations),
        "edges": edges,
        "label_type": label_t,
        "labels": comm[label_t].astype(np.int32),
        "num_classes": classes,
        "shortfall": shortfall,
    }


def count_report(g: dict, spec: dict) -> str:
    """One line: generated node and edge counts beside the published ones."""
    parts = []
    for t, v in spec["nodes"].items():
        parts.append(f"{t} {g['num_nodes'][t]}/{v['count']}")
    for r in spec["relations"]:
        parts.append(f"{r['name']} {len(g['edges'][r['name']][0])}/{r['edges']}")
    return "generated/published: " + ", ".join(parts)
