"""Compile the main path's kernels for a described TPU v5e — no chip needed.

The TPU compiler is installed with JAX; it compiles for a chip that is
described and not attached, and refuses what Mosaic or the chip would
refuse (unaligned slices, scalar or vector memory overruns). Shapes come
from a real ``pipeline.prepare`` of HAN on synthetic DBLP at ``scale=1.0``.
Each test asserts a ``tpu_custom_call`` (a Mosaic kernel) in the compiled
HLO.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file. ``jax.default_backend()`` still reports the CPU here, so the
``mosaic`` fixture steers the kernels' one backend decision
(``repro.kernels.common.interpret_mode``) to Mosaic for these tests only.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

H, DH, K = 8, 8, 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mosaic(topo):
    """Kernels lower through Mosaic; jit caches traced on the CPU path are
    dropped before and after, and the persistent cache stays off."""
    from repro.kernels import common

    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(common, "interpret_mode", lambda: False)
        jax.clear_caches()
        yield
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def dblp():
    from repro.core import pipeline

    return pipeline.prepare("han", "dblp", scale=1.0)


def _sds(x, sharding):
    return jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype, sharding=sharding)


def _assert_mosaic(compiled):
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()


@pytest.mark.parametrize("graph", [0, 1], ids=["APA", "APVPA"])
def test_grouped_pair_compiles_for_v5e(graph, dblp, one_chip, mosaic):
    """The served NA program of one semantic graph: θ gathers, grouped K1,
    K2 and the inverse-permutation gather."""
    from repro.kernels.fused_prune_aggregate import ops

    sg = dblp.sgs[graph]
    lay = sg.grouped()
    meta, agg_meta, k_s = ops.grouped_meta(lay, K)
    n = dblp.graph.num_nodes["author"]
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    args = (
        f32(n, H, DH), f32(n, H), f32(sg.num_targets, H), None,
        _sds(lay.nbr, one_chip), _sds(lay.msk.astype(np.int32), one_chip),
        _sds(lay.ety, one_chip), _sds(lay.row_targets, one_chip),
        _sds(meta, one_chip), _sds(agg_meta, one_chip), _sds(lay.perm, one_chip),
    )
    compiled = ops._grouped_call.lower(
        *args, k_s=k_s, t_tile=lay.t_tile, w=lay.w, slope=0.2, use_rel=False
    ).compile()
    _assert_mosaic(compiled)


def test_flat_pair_compiles_for_v5e(dblp, one_chip, mosaic):
    """The flat (T, D) pair at the APA graph's padded-CSC width."""
    from repro.kernels.fused_prune_aggregate.kernel import (
        fused_prune_aggregate_pallas,
    )

    sg = dblp.sgs[0]
    t, d = sg.num_targets, max(b.capacity for b in sg.buckets)
    n = dblp.graph.num_nodes["author"]
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    compiled = fused_prune_aggregate_pallas.lower(
        f32(t, d, H), i32(t, d), f32(t, H), i32(t, d), f32(n, H, DH), prune_k=K
    ).compile()
    _assert_mosaic(compiled)


def test_sharded_body_compiles_for_v5e(dblp, topo, mosaic):
    """The 4-way ``shard_map`` body on a mesh of the described chips: one
    grouped pair per shard over the stacked shard layout."""
    from repro.kernels.fused_prune_aggregate import ops

    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    sharded, rep = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    sg = dblp.sgs[0]
    sl = sg.sharded(4)
    (nbr, msk, ety, row_targets, _), (meta, agg_meta, k_s) = ops._sharded_device(
        sl, K
    )
    n = dblp.graph.num_nodes["author"]
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=rep)
    args = tuple(_sds(a, sharded) for a in (nbr, msk, ety, row_targets, meta, agg_meta))
    args += (f32(n, H, DH), f32(n, H), f32(sg.num_targets, H))
    fn = ops._sharded_fn(mesh, "data", False, k_s, sl.t_tile, sl.w, 0.2)
    compiled = fn.lower(*args).compile()
    _assert_mosaic(compiled)
    assert "num_partitions=4" in compiled.as_text()
