"""The ``HGNNModel`` protocol: one interface for every HGNN architecture.

All three models (HAN, RGAT, Simple-HGN) implement:

  * ``init(key, spec) -> params`` — parameters from a hashable
    :class:`~repro.core.batch.ModelSpec`;
  * ``apply(params, batch, flow) -> logits`` — the full forward pass over
    one :class:`~repro.core.batch.GraphBatch`;
  * ``layer_steps(params, batch, flow)`` — an iterator yielding each
    layer's (FP -> NA-per-semantic-graph -> fuse) stages as composable
    callables.

``apply`` is defined HERE, as the canonical composition of
``layer_steps`` + ``readout`` — so "running the yielded stages manually"
and "calling apply" are the same program by construction, and a scheduler
that re-orders stages (e.g. overlapping one layer's NA with the next
layer's FP across a mesh — the ROADMAP's multi-layer pipelining item)
starts from callables that provably reproduce the model.

The stage granularity is the paper's: ``project`` is the layer's Feature
Projection (one global projected table), each ``na`` entry is ONE
semantic graph's Neighbor Aggregation (one dispatch — a single grouped
kernel launch under ``fused_kernel``), and ``fuse`` is the semantic
fusion / type-wise combination that closes the layer. NA callables only
depend on the layer's projected table ``h``, never on each other, so they
are safe to run concurrently or shard independently.

``MODELS`` is the model registry (mirroring ``repro.data.datasets``'s
dataset registry): ``pipeline.prepare`` is table-driven over it instead
of an if/elif ladder, and external code can :func:`register_model` new
architectures without touching the pipeline.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Iterator, Tuple

import jax

from repro.core import flows
from repro.core.batch import GraphBatch, ModelSpec
from repro.core.flows import FlowConfig

# A layer stage's carry is model-defined (a per-type activation dict for
# relation/union models, the fused embedding for HAN); only the protocol's
# loop shape is fixed.
Carry = object


@dataclasses.dataclass(frozen=True)
class LayerStep:
    """One layer's stages as independent callables.

    ``project(carry) -> h`` — the layer's Feature Projection: per-type
    activations to the (N, H, dh) global projected table.

    ``na`` — ``(semantic_graph_name, fn)`` pairs, in the model's dispatch
    order; ``fn(h) -> z`` runs that one semantic graph's score
    decomposition + Neighbor Aggregation (one NA dispatch). Entries are
    mutually independent given ``h``.

    ``fuse(carry, h, zs) -> carry'`` — semantic fusion / per-type
    combination closing the layer; ``zs`` maps semantic-graph name to its
    NA output.
    """

    index: int
    project: Callable[[Carry], jax.Array]
    na: Tuple[Tuple[str, Callable[[jax.Array], jax.Array]], ...]
    fuse: Callable[[Carry, jax.Array, Dict[str, jax.Array]], Carry]


class HGNNModel:
    """Base class / protocol all HGNN models implement."""

    def init(self, key, spec: ModelSpec):
        raise NotImplementedError

    def layer_steps(
        self, params, batch: GraphBatch, flow: FlowConfig = FlowConfig()
    ) -> Iterator[LayerStep]:
        raise NotImplementedError

    def readout(self, params, batch: GraphBatch, carry: Carry) -> jax.Array:
        """Final carry -> (num_targets, num_classes) logits."""
        raise NotImplementedError

    def ego_globals(self, params, batch: GraphBatch, flow: FlowConfig):
        """Graph-global quantities an ego-subgraph forward cannot recompute
        from a sliced neighborhood alone, as a ``{name: array}`` dict (or
        ``None``). Computed ONCE per weight version on the full batch and
        injected into every :class:`~repro.core.ego.EgoBatch`, where layer
        stages pick them up via ``batch.ego_globals``. RGAT / Simple-HGN are
        fully row-local and need none; HAN overrides this with its
        semantic-attention β (a mean over ALL targets)."""
        return None

    def apply(
        self, params, batch: GraphBatch, flow: FlowConfig = FlowConfig()
    ) -> jax.Array:
        """The canonical forward pass: fold ``layer_steps`` then ``readout``.

        Wrapped in one ``flows.mesh_scope()`` so the ambient mesh is
        resolved AT MOST ONCE per apply (and not at all for flows that
        never consult it), however many NA dispatches the model issues.
        The stages carry the compile-time scopes ``fp`` (projection) and
        ``fusion`` (fuse and readout); each NA dispatch carries
        ``na.<semantic graph>`` (``flows.run_aggregate_graph``).
        """
        with flows.mesh_scope():
            carry: Carry = dict(batch.features)
            for step in self.layer_steps(params, batch, flow):
                with jax.named_scope("fp"):
                    h = step.project(carry)
                zs = {name: fn(h) for name, fn in step.na}
                with jax.named_scope("fusion"):
                    carry = step.fuse(carry, h, zs)
            with jax.named_scope("fusion"):
                return self.readout(params, batch, carry)


# ---------------------------------------------------------------------------
# Model registry (the dataset-registry pattern, applied to architectures)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ModelEntry:
    """How ``pipeline.prepare`` assembles one architecture.

    ``factory`` builds the (stateless) model object; ``sgb_kind`` names the
    Semantic Graph Build the model consumes (``"metapath"`` — needs a
    metapath table, ``"relation"`` — one graph per relation, ``"union"`` —
    one per destination type with edge-type ids).
    """

    name: str
    factory: Callable[[], HGNNModel]
    sgb_kind: str

    @property
    def needs_metapaths(self) -> bool:
        return self.sgb_kind == "metapath"


MODELS: Dict[str, ModelEntry] = {}


def register_model(
    name: str, factory: Callable[[], HGNNModel], sgb_kind: str
) -> None:
    """Register an architecture under ``name`` (overwrites)."""
    assert sgb_kind in ("metapath", "relation", "union"), sgb_kind
    MODELS[name] = ModelEntry(name=name, factory=factory, sgb_kind=sgb_kind)


def get_entry(name: str) -> ModelEntry:
    try:
        return MODELS[name]
    except KeyError:
        raise ValueError(
            f"unknown model {name!r}; registered: {sorted(MODELS)}"
        ) from None


def available() -> Tuple[str, ...]:
    return tuple(sorted(MODELS))
