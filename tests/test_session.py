"""HGNNModel protocol + GraphBatch pytree + InferenceSession contracts.

Covers the API-redesign migration:
  * ``model.apply(params, batch, flow)`` and the legacy ``task.logits``
    shim produce bit-identical logits for all 3 models;
  * running the stages ``layer_steps`` yields MANUALLY (project → NA per
    semantic graph → fuse, then readout) reproduces ``apply`` bit-for-bit
    — the contract the mesh-pipelining scheduler will build on;
  * ``GraphBatch`` is a real pytree: feature leaves trace through jit,
    static graph handles ride in the treedef with identity caching;
  * ``task.compile(flow)`` sessions are bit-identical to the jitted
    legacy path, cached per (flow, mesh, dtype), and their repeated calls
    do ZERO Python NA dispatch and ZERO ambient-mesh lookups;
  * the eager path's mesh resolution is hoisted: one lookup per apply,
    not one per semantic-graph dispatch;
  * ``train_hgnn``'s update step is cached (no re-jit across calls).
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import flows, pipeline
from repro.core.batch import ModelSpec
from repro.core.flows import FlowConfig
from repro.core.models import MODELS, get_entry
from repro.kernels.fused_prune_aggregate import kernel as fpa_kernel

TASKS = [("han", "acm"), ("rgat", "imdb"), ("simple_hgn", "dblp")]
FLOWS = [
    FlowConfig("staged"),
    FlowConfig("fused", prune_k=8),
    FlowConfig("fused_kernel", prune_k=8),
]


def _reset():
    flows.DISPATCH.update(
        graph_calls=0, bucket_calls=0, traces=0, sharded_calls=0,
        mesh_lookups=0,
    )
    fpa_kernel.DISPATCH.update(pallas_calls=0, grouped_traces=0)


@pytest.fixture(scope="module")
def tasks():
    return {
        (m, d): pipeline.prepare(m, d, scale=0.04, max_degree=48, seed=0)
        for m, d in TASKS
    }


# ---------------------------------------------------------------------------
# protocol migration
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("model,dataset", TASKS)
@pytest.mark.parametrize("flow", FLOWS, ids=lambda f: f.flow)
def test_apply_matches_legacy_shim(tasks, model, dataset, flow):
    task = tasks[(model, dataset)]
    new = np.asarray(task.model.apply(task.params, task.batch, flow))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        old = np.asarray(task.logits(task.params, flow))
    np.testing.assert_array_equal(new, old)


@pytest.mark.parametrize("model,dataset", TASKS)
def test_layer_steps_manual_composition(tasks, model, dataset):
    """Folding the yielded stages by hand == apply, bit for bit."""
    task = tasks[(model, dataset)]
    flow = FlowConfig("fused", prune_k=8)
    carry = dict(task.batch.features)
    n_steps = 0
    for step in task.model.layer_steps(task.params, task.batch, flow):
        h = step.project(carry)
        zs = {name: fn(h) for name, fn in step.na}
        carry = step.fuse(carry, h, zs)
        n_steps += 1
    manual = np.asarray(task.model.readout(task.params, task.batch, carry))
    direct = np.asarray(task.model.apply(task.params, task.batch, flow))
    np.testing.assert_array_equal(manual, direct)
    assert n_steps == task.model.num_layers


@pytest.mark.parametrize("model,dataset", TASKS)
def test_layer_steps_structure(tasks, model, dataset):
    """Every layer exposes one NA callable per semantic graph, named by it,
    and NA entries are independent given h (reordering them cannot change
    fuse's input dict)."""
    task = tasks[(model, dataset)]
    steps = list(task.model.layer_steps(task.params, task.batch))
    sg_names = {sg.name for sg in task.batch.sgs}
    for step in steps:
        assert {name for name, _ in step.na} == sg_names
        assert callable(step.project) and callable(step.fuse)
    assert [s.index for s in steps] == list(range(len(steps)))


def test_model_registry_mirrors_models():
    assert set(MODELS) >= {"han", "rgat", "simple_hgn"}
    assert get_entry("han").needs_metapaths
    assert not get_entry("rgat").needs_metapaths
    with pytest.raises(ValueError, match="unknown model"):
        get_entry("no_such_model")
    with pytest.raises(ValueError, match="unknown model"):
        pipeline.prepare("no_such_model", "acm", scale=0.03)


# ---------------------------------------------------------------------------
# GraphBatch pytree
# ---------------------------------------------------------------------------


def test_graphbatch_pytree_roundtrip(tasks):
    batch = tasks[("han", "acm")].batch
    leaves, treedef = jax.tree_util.tree_flatten(batch)
    assert all(isinstance(l, jax.Array) for l in leaves)
    assert len(leaves) == len(batch.features)
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert rebuilt.sgs is batch.sgs
    assert rebuilt.node_types == batch.node_types
    assert rebuilt._static is batch._static
    # flatten is stable: same batch -> identical treedef (jit cache key)
    assert jax.tree_util.tree_flatten(batch)[1] == treedef


def test_graphbatch_traces_through_jit(tasks):
    """apply jits with the batch as a TRACED argument (features are
    leaves, graphs are static) and caches on batch identity."""
    task = tasks[("han", "acm")]
    flow = FlowConfig("fused", prune_k=8)
    traces = []

    @jax.jit
    def fwd(p, b):
        traces.append(1)
        return task.model.apply(p, b, flow)

    a = np.asarray(fwd(task.params, task.batch))
    b = np.asarray(fwd(task.params, task.batch))  # same batch: cache hit
    np.testing.assert_array_equal(a, b)
    assert len(traces) == 1
    np.testing.assert_array_equal(
        a, np.asarray(task.model.apply(task.params, task.batch, flow))
    )


def test_modelspec_hashable(tasks):
    spec = tasks[("rgat", "imdb")].spec
    assert hash(spec) == hash(spec)
    assert spec.feat_dim_map == {
        t: d for t, d in spec.feat_dims
    }
    assert isinstance(spec, ModelSpec)


# ---------------------------------------------------------------------------
# InferenceSession
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("model,dataset", TASKS)
def test_session_matches_jitted_apply(tasks, model, dataset):
    """The AOT executable is bit-identical to the jitted legacy program
    (same trace, ahead-of-time compiled)."""
    task = tasks[(model, dataset)]
    flow = FlowConfig("fused", prune_k=8)
    sess = task.compile(flow)
    ref = np.asarray(
        jax.jit(lambda p: task.model.apply(p, task.batch, flow))(task.params)
    )
    np.testing.assert_array_equal(np.asarray(sess(task.params)), ref)
    # and within float tolerance of the eager legacy dispatch (op-by-op
    # execution may round the last ULP differently than the fused program)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        eager = np.asarray(task.logits(task.params, flow))
    np.testing.assert_allclose(np.asarray(sess(task.params)), eager, atol=5e-5)


def test_session_zero_python_dispatch(tasks):
    """Repeated session calls never re-enter the Python NA dispatch layer:
    no run_aggregate_graph entries, no mesh lookups, no retraces."""
    task = tasks[("rgat", "imdb")]
    sess = task.compile(FlowConfig("fused_kernel", prune_k=8))
    sess(task.params)  # build/warm
    _reset()
    for _ in range(3):
        jax.block_until_ready(sess(task.params))
    assert flows.DISPATCH["graph_calls"] == 0
    assert flows.DISPATCH["mesh_lookups"] == 0
    assert flows.DISPATCH["traces"] == 0
    assert fpa_kernel.DISPATCH["grouped_traces"] == 0


def test_session_refuses_bucketed_fused_on_tpu(tasks, monkeypatch):
    """On a TPU the single-program bucketed ``fused`` flow (known to hang
    there) is refused before lowering; the per-bucket loop and the other
    flows are not, and nothing is refused off the TPU."""
    from repro.core import session as session_mod

    task = tasks[("han", "acm")]
    fused = FlowConfig("fused", prune_k=8)
    session_mod.refuse_known_tpu_hang(task.batch, fused)  # cpu: allowed
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(NotImplementedError, match="known to hang"):
        session_mod.InferenceSession(
            task.model, task.batch, fused, params=task.params
        )
    for ok in (
        FlowConfig("fused", prune_k=8, bucket_dispatch="loop"),
        FlowConfig("staged_pruned", prune_k=8),
        FlowConfig("fused_kernel", prune_k=8),
    ):
        session_mod.refuse_known_tpu_hang(task.batch, ok)


def test_session_cache_keyed_on_flow(tasks):
    task = tasks[("han", "acm")]
    a = task.compile(FlowConfig("fused", prune_k=8))
    b = task.compile(FlowConfig("fused", prune_k=8))
    c = task.compile(FlowConfig("fused", prune_k=4))
    assert a is b and a is not c


def test_session_cache_keyed_on_matmul_precision(tasks):
    """A session compiled under jax.default_matmul_precision is its own
    program, not the cached default-precision one."""
    task = tasks[("han", "acm")]
    flow = FlowConfig("fused", prune_k=8)
    a = task.compile(flow)
    with jax.default_matmul_precision("highest"):
        b = task.compile(flow)
        assert task.compile(flow) is b
    assert a is not b and task.compile(flow) is a


def test_session_batch_call(tasks):
    task = tasks[("han", "acm")]
    flow = FlowConfig("fused", prune_k=8)
    sess = task.compile(flow)
    outs = sess.batch([task.params, task.params])
    assert len(outs) == 2
    np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(outs[1]))


# ---------------------------------------------------------------------------
# mesh-lookup hoist + train-step reuse
# ---------------------------------------------------------------------------


def test_mesh_lookup_hoisted_once_per_apply(tasks):
    """The eager fused_kernel path resolves the ambient mesh ONCE per
    forward, however many semantic graphs dispatch (rgat: R graphs x 3
    layers), and the jnp flows never resolve it at all."""
    task = tasks[("rgat", "imdb")]
    assert len(task.sgs) * task.model.num_layers > 1
    _reset()
    task.model.apply(task.params, task.batch, FlowConfig("fused_kernel", prune_k=8))
    assert flows.DISPATCH["mesh_lookups"] == 1
    assert flows.DISPATCH["graph_calls"] == len(task.sgs) * task.model.num_layers
    _reset()
    task.model.apply(task.params, task.batch, FlowConfig("fused", prune_k=8))
    assert flows.DISPATCH["mesh_lookups"] == 0


def test_train_step_cached_across_calls(tasks):
    task = tasks[("han", "acm")]
    flow = FlowConfig("fused", prune_k=8)
    s1, _ = task._train_step(flow, 5e-3)
    s2, _ = task._train_step(flow, 5e-3)
    assert s1 is s2
    s3, _ = task._train_step(flow, 1e-3)
    assert s1 is not s3
    # and the end-to-end path still learns through the cached step
    params = pipeline.train_hgnn(task, steps=5, lr=5e-3, flow=flow)
    assert np.isfinite(
        float(jnp.sum(task.model.apply(params, task.batch, flow)))
    )


def test_accuracy_splits_share_one_session(tasks):
    task = tasks[("han", "acm")]
    flow = FlowConfig("fused", prune_k=6)  # not compiled by earlier tests
    n0 = len(task._sessions)
    acc_v = pipeline.accuracy(task, task.params, flow, split="val")
    acc_t = pipeline.accuracy(task, task.params, flow, split="test")
    assert 0.0 <= acc_v <= 1.0 and 0.0 <= acc_t <= 1.0
    assert len(task._sessions) == n0 + 1  # one executable for both splits


def test_logits_shim_deprecation_warns_once():
    task = pipeline.prepare("han", "acm", scale=0.03, max_degree=32, seed=0)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        task.logits(task.params)
        task.logits(task.params)
    deps = [w for w in rec if issubclass(w.category, DeprecationWarning)]
    assert len(deps) == 1  # once per task, not once per call
