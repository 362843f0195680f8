"""``InferenceSession`` — the AOT-compiled serving entry point.

The paper's operation-fusion flow exists to kill per-stage dispatch
overhead at inference time; this module kills the HOST side of it. The
legacy path (``task.logits(params, flow)``) re-pays Python overhead on
every call: per-type eager projection ops, one ``run_aggregate_graph``
entry per semantic graph (each with jit-cache lookups, device-table cache
fetches, and — before the hoist — an ambient-mesh resolution walk), eager
fusion glue. An ``InferenceSession`` resolves everything ONCE at build:

  * the ambient mesh / shard layouts / device tables are resolved at
    session construction and pinned (``flows.mesh_scope(pinned=...)``), so
    even tracing does zero ambient-mesh walks;
  * the whole forward pass is AOT-lowered and compiled into ONE executable
    (``jax.jit(...).lower(params).compile()``) whose activations live and
    die inside the XLA program (buffer-reuse/donation is XLA's, not
    Python's, problem) — per ``(flow, mesh, dtype)``, cached by
    ``HGNNTask.compile``;
  * ``session(params)`` / ``session.batch(params_list)`` dispatch that
    executable directly: zero per-call mesh lookups, zero Python bucket
    dispatch, zero retrace risk (a shape/dtype mismatch is a loud error,
    never a silent recompile).

``benchmarks/session_overhead.py`` asserts the contract: bit-identical
logits to the legacy path for every model × flow (sharded mesh included)
and ≥ 2x lower per-call host overhead on repeated inference.

``donate_params=True`` additionally donates the parameter buffers to the
executable — for serving patterns that stream in fresh weights each call
(the caller's arrays are INVALIDATED; never use it with params you reuse).

QUERY-SLICED SERVING (``session.query``): production traffic is not "give
me every target's logits" — it is thousands of concurrent requests each
asking for a HANDFUL of target vertices (possibly under different weight
versions). ``session.query(params, idx)`` serves one padded query block:
``idx`` is an int32 vector of target ids whose length is the block's
CAPACITY, and the call returns the ``(capacity, num_classes)`` logits rows
for those ids. Two-stage by design: the block dispatches THE session
executable (the same compiled forward every path runs — which is what
makes microbatched, serial, and full-forward results bit-identical BY
CONSTRUCTION; a fused forward+slice program would let XLA re-fuse the
forward differently per capacity, observed 1-ULP drift under
``fused_kernel``), then a tiny per-capacity gather program slices the
requested rows on device. Gather programs are AOT-compiled per capacity
and cached, so a front-end that pads every microbatch to a capacity from
a fixed bucket ladder — see ``repro.serve`` — never retraces ANY
program: request batching reuses the degree-bucket idea (pad to the
tightest capacity) at the REQUEST level. The per-block cost is one full
forward regardless of how many requests share the block, which is
exactly why microbatching pays (and why the future ego-subgraph
extraction path keeps the same entry point: extracted ego-batches are
query blocks whose forward stage shrinks to O(neighborhood)).
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core import flows
from repro.core.batch import GraphBatch
from repro.core.flows import FlowConfig
from repro.core.hetgraph import BucketedSemanticGraph
from repro.distributed import sharding as dist

_UNSET = object()


def mesh_fingerprint(gm) -> Optional[Tuple]:
    """Hashable identity of a resolved ``dist.graph_mesh()`` result, for
    keying session caches: ``None`` (no mesh) or (mesh, axis, size)."""
    if gm is None:
        return None
    mesh, axis, n = gm
    return (mesh, axis, n)


def refuse_known_tpu_hang(batch: GraphBatch, flow: FlowConfig) -> None:
    """Refuse a session whose program is known to hang on a TPU.

    On a TPU v5e (jax 0.9.0) the single-program bucketed ``fused`` NA
    (``flows._bucketed_aggregate``) of HAN on synthetic DBLP at
    ``scale=1.0`` compiled but never finished, while each bucket's own
    program ran. The cause is not known, so every such session is refused
    on a TPU: a serving fallback that stalls on a breaker trip is worse
    than one that is never built. ``bucket_dispatch="loop"`` and the other
    flows are unaffected.
    """
    if (
        flow.flow == "fused"
        and flow.bucket_dispatch == "single"
        and jax.default_backend() == "tpu"
        and any(isinstance(sg, BucketedSemanticGraph) for sg in batch.sgs)
    ):
        raise NotImplementedError(
            "the single-program bucketed 'fused' flow is known to hang on "
            "TPU (TPU v5e, DBLP scale=1.0); use 'fused_kernel', "
            "'staged_pruned' or FlowConfig(..., bucket_dispatch='loop')"
        )


def _gather(out, idx):
    """The query rows of a forward's output, under the scope ``gather``."""
    with jax.named_scope("gather"):
        return out[idx]


class InferenceSession:
    """One AOT-compiled executable serving ``model.apply`` for one batch.

    Build once (``task.compile(flow)`` is the cached front door), call many
    times. The compiled program is specialized to the parameter avals it
    was lowered with — pass params of the same tree/shape/dtype.
    """

    def __init__(
        self,
        model,
        batch: GraphBatch,
        flow: FlowConfig = FlowConfig(),
        params=None,
        mesh_info=_UNSET,
        donate_params: bool = False,
    ):
        if params is None:
            raise ValueError(
                "InferenceSession needs example params to AOT-lower against"
            )
        refuse_known_tpu_hang(batch, flow)
        if mesh_info is _UNSET:
            # the session's single mesh resolution — every traced NA
            # dispatch below reuses it via the pinned scope
            mesh_info = dist.graph_mesh()
        self.model = model
        self.graph_batch = batch
        self.flow = flow
        self.mesh_info = mesh_info
        self.donate_params = donate_params

        def fn(p):
            with flows.mesh_scope(pinned=mesh_info):
                return model.apply(p, batch, flow)

        self._jitted = jax.jit(
            fn, donate_argnums=(0,) if donate_params else ()
        )
        with tracing.counting() as sites:
            self.lowered = self._jitted.lower(params)
        self._executable = self.lowered.compile()
        tracing.record_scopes(self._executable, sites)
        # query-sliced serving state: the output aval (shape/dtype AND
        # sharding, so gather programs accept the executable's committed
        # output under a mesh) plus one cached gather program per block
        # capacity
        self._out_aval = self._output_aval(fn, params)
        self._gathers: dict = {}
        # ego-subgraph serving state (enable_ego / query_ego): the attached
        # planner, one compiled executable per EgoSignature, and the
        # per-weight-version ego_globals cache
        self._ego = None
        self._ego_exes: dict = {}
        self._ego_globals_cache = None

    def __call__(self, params) -> jax.Array:
        """(num_targets, num_classes) logits; one executable dispatch."""
        with tracing.span("session.forward"):
            return self._executable(params)

    # -- query-sliced serving ---------------------------------------------
    def _output_aval(self, fn, params):
        """Aval of the forward output, including the compiled executable's
        output sharding, so gather programs lowered against it accept the
        executable's committed output directly (mesh or not)."""
        sds = jax.eval_shape(fn, params)
        return jax.ShapeDtypeStruct(
            sds.shape, sds.dtype, sharding=self._executable.output_shardings
        )

    def compile_query(self, capacity: int):
        """The AOT gather program serving ``(capacity,)`` query blocks:
        built once per capacity (cheap — it lowers ``out[idx]`` against
        the forward's output aval, NOT another full forward), cached on
        the session. A serving front-end pre-warms its whole capacity
        ladder with this before taking traffic
        (``repro.serve.ServeFrontend`` does)."""
        capacity = int(capacity)
        if capacity < 1:
            raise ValueError(f"query capacity must be >= 1, got {capacity}")
        exe = self._gathers.get(capacity)
        if exe is None:
            exe = jax.jit(_gather).lower(
                self._out_aval,
                jax.ShapeDtypeStruct((capacity,), jnp.int32),
            ).compile()
            tracing.record_scopes(exe)
            self._gathers[capacity] = exe
        return exe

    def query(self, params, idx) -> jax.Array:
        """Logits for one padded query block: ``idx`` is an int32 vector of
        target ids (length = the block capacity), the result is the
        ``(len(idx), num_classes)`` rows ``session(params)[idx]`` —
        BIT-IDENTICAL to slicing the full-forward output, because it IS
        the full-forward executable plus a cached on-device gather (the
        forward output never visits the host between the two dispatches).
        Padded slots should repeat a valid id; callers discard their
        rows."""
        idx = jnp.asarray(idx, jnp.int32)
        if idx.ndim != 1:
            raise ValueError(f"query block must be a 1-D id vector, got "
                             f"shape {idx.shape}")
        gather = self.compile_query(idx.shape[0])
        with tracing.span("session.query", capacity=idx.shape[0]):
            out = self._executable(params)
            flows.DISPATCH["query_calls"] += 1
            return gather(out, idx)

    def prewarm(self, capacities: Sequence[int]) -> "InferenceSession":
        """Pre-compile the gather ladder for every capacity in one shot.

        This is the FALLBACK-FLOW pre-compilation hook: a fault-tolerant
        front-end (``repro.serve.ServeFrontend(fallback=...)``) prewarms
        both its primary and its degradation session at construction, so
        a circuit-breaker trip mid-incident swaps executables — it never
        compiles anything. Returns self for chaining
        (``task.compile(fallback_flow).prewarm(policy.capacities)``)."""
        for cap in capacities:
            self.compile_query(cap)
        return self

    # -- ego-subgraph serving ---------------------------------------------
    def enable_ego(self, planner=None, **planner_kw) -> "InferenceSession":
        """Attach an :class:`~repro.core.ego.EgoPlanner` so ``query_ego``
        can serve blocks at O(neighborhood). With no explicit ``planner``,
        builds one from this session's batch with ``depth =
        model.num_layers`` (extra kwargs — ``capacities``, ``features``
        for out-of-core host tables, ``sample_sizes`` — pass through).
        Returns self for chaining."""
        if planner is None:
            from repro.core.ego import EgoPlanner

            depth = getattr(self.model, "num_layers", None)
            if depth is None:
                raise ValueError(
                    "model exposes no num_layers; pass an EgoPlanner "
                    "built with an explicit depth"
                )
            planner = EgoPlanner(self.graph_batch, depth=depth, **planner_kw)
        self._ego = planner
        return self

    @property
    def ego_planner(self):
        """The attached planner (``None`` until ``enable_ego``)."""
        return self._ego

    def _ego_globals_for(self, params):
        """``model.ego_globals`` cached per weight version (by parameter
        tree identity — a ``WeightPlane``-routing front-end caches per
        tenant version itself and passes the result in)."""
        ent = self._ego_globals_cache
        if ent is None or ent[0] is not params:
            ent = (params, self.model.ego_globals(params, self.graph_batch, self.flow))
            self._ego_globals_cache = ent
        return ent[1]

    def compile_ego(self, ego_batch, params):
        """The AOT ego executable for ``ego_batch``'s signature: the model
        forward over the O(neighborhood) batch fused with the
        ``out_rows`` gather, traced ONCE per :class:`EgoSignature` (shapes
        sit on the planner's capacity ladders, so the cache stays small)
        and cached on the session. The mesh is pinned to ``None`` — ego
        forwards run replicated; sharding pays off on full-graph tables,
        not neighborhood-sized ones."""
        exe = self._ego_exes.get(ego_batch.sig)
        if exe is None:
            flows.DISPATCH["ego_traces"] += 1
            model, flow = self.model, self.flow

            def fn(p, b):
                with flows.mesh_scope(pinned=None):
                    return _gather(model.apply(p, b, flow), b.out_rows)

            with tracing.counting() as sites:
                lowered = jax.jit(fn).lower(params, ego_batch)
            exe = lowered.compile()
            tracing.record_scopes(exe, sites)
            self._ego_exes[ego_batch.sig] = exe
        return exe

    def adopt_ego_cache(self, other: "InferenceSession") -> int:
        """Adopt ``other``'s compiled ego executables (graph-version swap).

        Ego executables close over the model and flow only — every graph
        table rides in as an :class:`EgoBatch` pytree argument, and
        signatures are value-hashed shape statics — so an executable
        compiled on a previous graph version serves the successor
        unchanged. Requires the SAME model object and an equal flow;
        existing entries are never overwritten. Returns the adopted count
        (``DISPATCH["ego_traces"]`` does not tick for adopted entries —
        that counter is the proof clean closures were not retraced)."""
        if other.model is not self.model or other.flow != self.flow:
            raise ValueError(
                "ego executables are only portable between sessions "
                "sharing the model object and flow config"
            )
        adopted = 0
        for sig, exe in other._ego_exes.items():
            if sig not in self._ego_exes:
                self._ego_exes[sig] = exe
                adopted += 1
        return adopted

    def query_ego(self, params, idx, ego_globals=_UNSET) -> jax.Array:
        """Logits for one padded query block via the ego-subgraph path.

        Same contract as :meth:`query` — ``idx`` is an int32 id vector,
        the result its ``(len(idx), num_classes)`` logits rows — but the
        forward runs on the extracted L-hop neighborhood of ``idx``
        instead of the full graph, so per-call work scales with the query
        neighborhood (parity vs. :meth:`query` is ≤ 1e-5, not bit-exact:
        the ego program is a different XLA fusion over the same math).
        Queries whose closure exceeds the planner's top capacity fall
        back to :meth:`query` (``DISPATCH["ego_fallback"]``); ego batches
        whose neighbor widths all fit under ``prune_k`` compile through
        the paper's §4.3 pruner bypass (``DISPATCH["ego_bypass"]``)."""
        if self._ego is None:
            raise RuntimeError(
                "ego path not enabled — call session.enable_ego() first"
            )
        idx = np.asarray(idx, dtype=np.int32)
        if idx.ndim != 1:
            raise ValueError(
                f"query block must be a 1-D id vector, got shape {idx.shape}"
            )
        gl = self._ego_globals_for(params) if ego_globals is _UNSET else ego_globals
        eb = self._ego.extract(idx, ego_globals=gl)
        if eb is None:
            flows.DISPATCH["ego_fallback"] += 1
            return self.query(params, idx)
        exe = self.compile_ego(eb, params)
        flows.DISPATCH["ego_calls"] += 1
        if (
            self.flow.flow in ("fused", "fused_kernel")
            and self.flow.prune_k is not None
            and eb.sig.max_d_cap <= self.flow.prune_k
        ):
            flows.DISPATCH["ego_bypass"] += 1
        return exe(params, eb)

    @property
    def out_shape(self) -> Tuple[int, ...]:
        """Forward-output shape ``(num_targets, num_classes)`` — the
        compatibility contract a fallback session must share with the
        primary (same targets, same classes) to serve its query blocks."""
        return tuple(self._out_aval.shape)

    @property
    def query_capacities(self) -> Tuple[int, ...]:
        """Capacities with a compiled gather program, ascending."""
        return tuple(sorted(self._gathers))

    def batch(self, params_list: Sequence) -> List[jax.Array]:
        """Serve several parameter sets against the same compiled
        executable (e.g. an ensemble, or A/B weights)."""
        return [self._executable(p) for p in params_list]

    def cost_analysis(self):
        """XLA's per-call cost estimate for the compiled executable."""
        try:
            return self._executable.cost_analysis()
        except Exception:  # pragma: no cover - backend-dependent
            return None

    def __repr__(self):
        mesh = (
            f"{self.mesh_info[1]}:{self.mesh_info[2]}"
            if self.mesh_info is not None
            else "none"
        )
        return (
            f"InferenceSession(flow={self.flow.flow!r}, mesh={mesh}, "
            f"donate_params={self.donate_params})"
        )
