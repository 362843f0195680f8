"""End-to-end HGNN task assembly: dataset → SGB → model → GraphBatch.

This is the piece benchmarks/examples/tests share. ``prepare()`` is
TABLE-DRIVEN over the model registry (``repro.core.models.MODELS``,
mirroring the dataset registry): each registered architecture names its
SGB kind and factory, and the pipeline assembles dataset → semantic
graphs → :class:`~repro.core.batch.GraphBatch` →
:class:`~repro.core.batch.ModelSpec` → params identically for every model
— no per-model if/elif, no per-model apply signature.

The returned ``HGNNTask`` serves inference two ways:

  * ``task.compile(flow)`` → an AOT-compiled
    :class:`~repro.core.session.InferenceSession` (the serving path:
    one executable per (flow, mesh, dtype), zero per-call Python
    dispatch);
  * ``task.logits(params, flow)`` — the legacy closure-shaped entry,
    kept as a thin DEPRECATED shim over ``model.apply(params, batch,
    flow)`` for existing callers.
"""
from __future__ import annotations

import dataclasses
import os
import warnings
from typing import Dict, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hetgraph
from repro.core.batch import GraphBatch, ModelSpec
from repro.core.flows import FlowConfig
from repro.core.models import get_entry
from repro.core.session import InferenceSession, mesh_fingerprint
from repro.data import datasets, sgb_cache
from repro.distributed import sharding as dist_sharding


@dataclasses.dataclass
class HGNNTask:
    name: str
    model_name: str
    model: object
    graph: hetgraph.HetGraph
    batch: GraphBatch
    spec: ModelSpec
    params: dict
    labels: jax.Array
    splits: Dict[str, np.ndarray]
    sgs: list  # semantic graphs driving NA (for stats/benchmarks)
    # the builder arguments that produced ``sgs`` — what the streamed-delta
    # ingestor (repro.stream) needs to merge-upgrade the layouts in place
    # and what a from-scratch rebuild must replay for bit-parity
    sgb_kind: str = ""
    sgb_args: dict = dataclasses.field(default_factory=dict)
    metapaths: Optional[Dict[str, Sequence[str]]] = None
    _sessions: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False
    )
    _steps: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False
    )
    _warned_logits: bool = dataclasses.field(
        default=False, repr=False, compare=False
    )

    @property
    def num_edges(self) -> int:
        return int(sum(sg.num_edges for sg in self.sgs))

    def logits(self, params, flow: FlowConfig = FlowConfig()) -> jax.Array:
        """DEPRECATED shim over ``model.apply(params, batch, flow)``.

        Kept so pre-protocol callers keep working bit-for-bit; new code
        should call ``task.model.apply(params, task.batch, flow)`` for
        one-off traces or ``task.compile(flow)`` for repeated inference.
        """
        if not self._warned_logits:
            self._warned_logits = True
            warnings.warn(
                "HGNNTask.logits is deprecated: use "
                "task.model.apply(params, task.batch, flow) or "
                "task.compile(flow)",
                DeprecationWarning,
                stacklevel=2,
            )
        return self.model.apply(params, self.batch, flow)

    def compile(
        self,
        flow: FlowConfig = FlowConfig(),
        params=None,
        donate_params: bool = False,
    ) -> InferenceSession:
        """The cached AOT serving entry: ONE executable per (flow, mesh,
        dtype, donation, default matmul precision) — repeated calls
        (``accuracy`` over splits, a serving loop) share it. ``params`` only
        provides example avals for lowering (defaults to the task's init
        params)."""
        if params is None:
            params = self.params
        gm = dist_sharding.graph_mesh()
        # key on the full example avals (treedef + leaf shape/dtype), not
        # just dtypes: a compile(..., params=...) with a structurally
        # different tree must get its own executable, not a stale one
        leaves, treedef = jax.tree_util.tree_flatten(params)
        avals = tuple((l.shape, str(l.dtype)) for l in leaves)
        # the program is traced under the ambient
        # jax.default_matmul_precision, so that is part of its identity
        key = (flow, mesh_fingerprint(gm), treedef, avals, donate_params,
               jax.config.jax_default_matmul_precision)
        sess = self._sessions.get(key)
        if sess is None:
            sess = InferenceSession(
                self.model, self.batch, flow, params=params, mesh_info=gm,
                donate_params=donate_params,
            )
            self._sessions[key] = sess
        return sess

    def _train_step(self, flow: FlowConfig, lr: float, weight_decay: float = 1e-4):
        """One jitted (params, opt_state) -> (params, opt_state, loss) step,
        cached per (flow, lr, weight_decay) so repeated ``train_hgnn`` /
        resumed training never retrace."""
        key = (flow, float(lr), float(weight_decay))
        hit = self._steps.get(key)
        if hit is not None:
            return hit
        from repro.optim import adamw

        opt = adamw(lr=lr, weight_decay=weight_decay)
        tr = jnp.asarray(self.splits["train"])
        model, batch, labels = self.model, self.batch, self.labels

        def loss_fn(p):
            lg = model.apply(p, batch, flow)[tr]
            lab = labels[tr]
            logp = jax.nn.log_softmax(lg)
            return -jnp.take_along_axis(logp, lab[:, None], axis=1).mean()

        @jax.jit
        def step_fn(p, s):
            loss, grads = jax.value_and_grad(loss_fn)(p)
            p, s = opt.update(grads, s, p)
            return p, s, loss

        self._steps[key] = (step_fn, opt)
        return step_fn, opt


def _splits(n: int, seed: int = 0):
    """60/20/20 random split. For ``n >= 3`` every split is guaranteed
    non-empty (``int(0.2 * n)`` truncates to 0 on tiny graphs, which used
    to hand accuracy() an empty index set); the three splits always form a
    disjoint union of ``range(n)``."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(n)
    n_tr, n_va = int(0.6 * n), int(0.2 * n)
    if n >= 3:
        n_va = max(1, n_va)
        # test gets the remainder; keep it (and train) at least 1
        n_tr = max(1, min(n_tr, n - n_va - 1))
    out = {
        "train": perm[:n_tr],
        "val": perm[n_tr: n_tr + n_va],
        "test": perm[n_tr + n_va:],
    }
    if n >= 3:
        assert all(len(v) > 0 for v in out.values()), (n, n_tr, n_va)
    cover = np.sort(np.concatenate(list(out.values())))
    assert np.array_equal(cover, np.arange(n)), "splits must partition range(n)"
    return out


def prepare(
    model_name: str,
    dataset: datasets.DatasetSpec,
    scale: float = 0.1,
    max_degree: Optional[int] = 256,
    seed: int = 0,
    bucket_sizes: Union[Sequence[int], str, None] = hetgraph.DEFAULT_BUCKET_SIZES,
    shards: Optional[int] = None,
    sgb_cache_dir: Union[str, "os.PathLike[str]", None] = None,
    metapaths: Optional[Dict[str, Sequence[str]]] = None,
) -> HGNNTask:
    """Assemble dataset → SGB → model, table-driven over the model registry.

    ``model_name`` is looked up in ``repro.core.models.MODELS`` (register
    new architectures with ``repro.core.models.register_model``); the
    entry's ``sgb_kind`` selects the Semantic Graph Build and everything
    downstream is model-agnostic. ``dataset`` is resolved by
    ``repro.data.datasets.resolve`` and is interchangeably a registry name
    (synthetic generators, parameterized by ``scale``/``seed``), a path to
    an on-disk HGB/OGB-style dump directory, or a ``HetGraph`` instance;
    the graph is schema-validated either way. ``metapaths`` overrides the
    dataset's HAN metapath table (registry datasets ship one, dumps may
    carry one in meta.json; an in-memory ``HetGraph`` has none, so pass
    it here). ``bucket_sizes`` selects the
    SGB layout: a capacity list yields the degree-bucketed build (the
    default), ``"auto"`` autotunes each semantic graph's capacities from
    its own degree histogram (``hetgraph.autotune_bucket_sizes``), ``None``
    the flat (T, D_max) padded-CSC build. Bucketed layouts run NA as a
    single dispatch per semantic graph (one ragged-grid kernel launch under
    ``fused_kernel``); models are layout-agnostic.

    ``sgb_cache_dir`` switches SGB to the content-addressed artifact cache
    (``repro.data.sgb_cache.build_or_load``): the first prepare() for a
    given (graph structure, builder args, tile constants) builds and saves
    the bucketed stack + grouped/sharded layouts; every later process
    loads them instead of rebuilding.

    ``shards`` pre-partitions every bucketed semantic graph's grouped tile
    stack at build time (``BucketedSemanticGraph.sharded``): ``None``
    reads the ambient mesh's ``bucket_tiles`` axis size (no mesh → no
    pre-split; the sharded NA path still builds splits lazily on first
    dispatch), an int forces that split count. Inference under a mesh then
    pays zero build-time work per dispatch."""
    entry = get_entry(model_name)
    g, ds_name, mps = datasets.resolve(dataset, scale=scale, seed=seed)
    if metapaths is not None:
        mps = metapaths
    key = jax.random.PRNGKey(seed)

    if shards is None:
        gm = dist_sharding.graph_mesh()
        shards = gm[2] if gm is not None else 0
    sgb_kw = dict(
        max_degree=max_degree, seed=seed, bucket_sizes=bucket_sizes,
        cache_dir=sgb_cache_dir, shards=shards,
    )

    if entry.needs_metapaths:
        if not mps:
            raise ValueError(
                f"model {model_name!r} needs metapaths for dataset "
                f"{ds_name!r}: registry datasets define them; on-disk dumps "
                "carry them in meta.json"
            )
        built, _ = sgb_cache.build_or_load(
            g, entry.sgb_kind, metapaths=mps, **sgb_kw
        )
    else:
        built, _ = sgb_cache.build_or_load(g, entry.sgb_kind, **sgb_kw)
    sgs = list(built.values()) if isinstance(built, dict) else list(built)

    batch = GraphBatch.from_graph(g, sgs)
    spec = ModelSpec.from_graph(g, sgs)
    model = entry.factory()
    params = model.init(key, spec)

    if shards:
        # the kernel's tile constants, not hetgraph's generic defaults: the
        # sharded dispatch keys its layout cache on (n, T_TILE, W_TILE), so
        # pre-splitting with anything else would build a split no dispatch
        # ever reads. On a cache hit build_or_load already injected the
        # split; this is a no-op then (cached per layout).
        from repro.kernels.fused_prune_aggregate.kernel import T_TILE, W_TILE

        for sg in sgs:
            if isinstance(sg, hetgraph.BucketedSemanticGraph):
                sg.sharded(shards, T_TILE, W_TILE)

    return HGNNTask(
        name=f"{model_name}/{ds_name}",
        model_name=model_name,
        model=model,
        graph=g,
        batch=batch,
        spec=spec,
        params=params,
        labels=jnp.asarray(g.labels),
        splits=_splits(g.num_nodes[g.label_type], seed),
        sgs=sgs,
        sgb_kind=entry.sgb_kind,
        sgb_args=dict(
            max_degree=max_degree, seed=seed, bucket_sizes=bucket_sizes
        ),
        metapaths=dict(mps) if mps else None,
    )


def train_hgnn(
    task: HGNNTask,
    steps: int = 200,
    lr: float = 5e-3,
    flow: FlowConfig = FlowConfig(),
    log_every: int = 0,
):
    """Full-batch node-classification training (inference experiments in the
    paper run on trained models; we train in-framework). Always starts from
    ``task.params``; the jitted update step is cached on the task per
    (flow, lr), so calling ``train_hgnn`` again (a longer schedule, a
    hyperparameter re-run) reuses one compiled step instead of
    re-jitting."""
    step_fn, opt = task._train_step(flow, lr)
    params, state = task.params, opt.init(task.params)
    for i in range(steps):
        params, state, loss = step_fn(params, state)
        if log_every and (i % log_every == 0 or i == steps - 1):
            print(f"  step {i:4d} loss {float(loss):.4f}")
    return params


def accuracy(task: HGNNTask, params, flow: FlowConfig = FlowConfig(), split="test"):
    """Split accuracy via the task's cached ``InferenceSession`` — the
    val and test evaluations (and any repeated sweep over the same flow)
    share ONE compiled executable instead of re-dispatching the eager
    pipeline per call."""
    idx = jnp.asarray(task.splits[split])
    pred = task.compile(flow, params=params)(params)[idx].argmax(-1)
    return float((pred == task.labels[idx]).mean())
