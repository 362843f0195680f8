"""Chaos harness — the serving stack under DETERMINISTIC fault injection.

``benchmarks/serve_load.py`` proves the happy path (throughput + bit-exact
parity); this benchmark proves the SERVING CONTRACT under failure: no
future is ever stranded — under every injected fault class each submitted
request resolves with a result or a typed error from
``repro.serve.health``. Every scenario runs REAL compiled sessions
(``fused_kernel`` primary, ``fused`` fallback) on ``FakeClock`` +
``InlineExecutor``, so backoff sleeps, deadline expiries, and breaker
cooldowns are exact functions of the :class:`repro.serve.FaultPlan` —
zero real sleeps, zero thread races, reproducible to the row.

Scenarios committed to ``BENCH_chaos.json`` (all asserted; CI runs
``--smoke``):

  * ``chaos_transient_retry``   — flaky dispatch heals under capped
    exponential backoff (exact sleep schedule asserted), result stays
    BIT-EXACT to the primary full forward;
  * ``chaos_breaker_trip_recover`` — N consecutive primary failures trip
    the breaker; every degraded block is SERVED by the pre-compiled
    fallback flow (bit-exact to the fallback's own full forward — the
    paper's §6 accuracy budget is the license to swap flows, not to
    return garbage); after the cooldown the half-open probe recovers and
    rows are bit-exact to the primary again. Zero failed requests;
  * ``chaos_deadline_storm``    — a slow block pushes queued deadlined
    requests past expiry; they fail typed at the NEXT drain, never
    costing a forward, while undeadlined traffic is unaffected;
  * ``chaos_tenant_unpublish``  — a tenant unpublished between submit and
    checkout fails ONLY that block's futures (typed, breaker untouched);
    republishing restores service;
  * ``chaos_queue_saturation``  — a burst over ``max_pending`` sheds fast
    with ``QueueFullError``; every admitted request serves bit-exact;
  * ``chaos_sharded_breaker``   — (≥ 8 devices; the CI multidevice job
    sets ``--sharded``) trip → degrade → recover over 8-way mesh-sharded
    primary AND fallback sessions, parity asserted against both.

    PYTHONPATH=src:. python benchmarks/serve_chaos.py --smoke
"""
from __future__ import annotations

import argparse
import functools
import time

import jax
import numpy as np

from benchmarks.common import emit as _emit_to

emit = functools.partial(_emit_to, path="BENCH_chaos.json")
from repro.core import flows, pipeline
from repro.core.flows import FlowConfig
from repro.serve import (
    BatchPolicy,
    DeadlineExceededError,
    FakeClock,
    FaultPlan,
    InlineExecutor,
    QueueFullError,
    ServeFrontend,
    SupervisorPolicy,
    TenantUnpublishedError,
    WeightPlane,
)

PRUNE_K = 8
POLICY = BatchPolicy(capacities=(1, 4, 8), flush_timeout=0.01)


def _assert_no_stranded(futs):
    """THE chaos invariant: every future resolved, result or typed error."""
    for f in futs:
        assert f.done(), "stranded future — the serving contract is broken"
        f.exception(0)  # raises TimeoutError iff incomplete


def _frontend(sess, params, clock, fallback=None, supervisor=None,
              faults=None, policy=POLICY):
    return ServeFrontend(
        sess, params, policy, clock=clock, executor=InlineExecutor(),
        fallback=fallback, supervisor=supervisor, faults=faults,
    )


def _submit_blocks(fe, n_requests, size, num_targets, seed=0):
    rng = np.random.default_rng(seed)
    targets = [
        rng.integers(0, num_targets, size=size).tolist()
        for _ in range(n_requests)
    ]
    return targets, [fe.submit(t) for t in targets]


def scenario_transient_retry(model, task, sess, clock_unused):
    full = np.asarray(sess(task.params))
    plan = FaultPlan()
    plan.fail("dispatch", times=2)  # default TransientDispatchError
    sup = SupervisorPolicy(max_retries=2, backoff_base=1e-3, backoff_cap=0.1)
    clock = FakeClock()
    fe = _frontend(sess, task.params, clock, supervisor=sup, faults=plan)
    t0 = time.perf_counter()
    targets, futs = _submit_blocks(
        fe, 4, 2, task.batch.num_targets
    )  # one saturated block of 8
    assert fe.pump() == 1
    wall = time.perf_counter() - t0
    for t, f in zip(targets, futs):
        assert f.via == "primary"
        assert np.array_equal(f.result(0), full[t]), (
            f"{model}: retried block lost bit-exactness"
        )
    # exact retry schedule: two poisoned attempts -> 1ms, 2ms backoff
    assert fe.stats.retries == 2 and clock.sleeps == [1e-3, 2e-3], (
        fe.stats.retries, clock.sleeps,
    )
    assert fe.stats.failed == 0 and fe.breaker.trips == 0
    _assert_no_stranded(futs)
    fe.close()
    emit(
        f"chaos_transient_retry_{model}", wall / len(futs) * 1e6,
        f"retries={fe.stats.retries};backoff_sleeps=1ms,2ms"
        f";parity=bit_exact_primary;failed=0",
    )


def scenario_breaker_trip_recover(model, task, sess, fb_sess,
                                  emit_name=None, mesh_note=""):
    """3 consecutive primary failures -> OPEN -> every block served
    degraded (fallback bit-exact) -> cooldown -> half-open probe ->
    CLOSED, primary bit-exact again. ZERO failed requests end to end."""
    full_primary = np.asarray(sess(task.params))
    full_fallback = np.asarray(fb_sess(task.params))
    plan = FaultPlan()
    plan.fail("dispatch", RuntimeError("injected: device lost"),
              engine="primary", times=3)
    sup = SupervisorPolicy(
        max_retries=0, breaker_threshold=3, breaker_cooldown=0.05,
    )
    clock = FakeClock()
    fe = _frontend(sess, task.params, clock, fallback=fb_sess,
                   supervisor=sup, faults=plan)
    flows.DISPATCH["query_calls"] = 0
    t0 = time.perf_counter()

    # incident: 5 saturated blocks; 3 trip the breaker, all 5 SERVE
    targets, futs = _submit_blocks(fe, 20, 2, task.batch.num_targets, seed=1)
    assert fe.pump() == 5
    for t, f in zip(targets, futs):
        assert f.via == "fallback"
        assert np.array_equal(f.result(0), full_fallback[t]), (
            f"{model}: degraded block is not bit-exact to the fallback flow"
        )
    assert fe.breaker.state == "open" and fe.breaker.trips == 1
    assert fe.stats.fallback_blocks == 5 and fe.stats.failed == 0
    assert not fe.health().healthy and fe.health().live

    # cooldown elapses -> the next block is the half-open probe; the
    # fault budget is spent, so the primary succeeds and the breaker
    # recovers
    clock.advance(sup.breaker_cooldown)
    targets2, futs2 = _submit_blocks(fe, 8, 2, task.batch.num_targets, seed=2)
    assert fe.pump() == 2
    wall = time.perf_counter() - t0
    for t, f in zip(targets2, futs2):
        assert f.via == "primary"
        assert np.array_equal(f.result(0), full_primary[t]), (
            f"{model}: recovered block is not bit-exact to the primary flow"
        )
    assert fe.breaker.state == "closed" and fe.breaker.recoveries == 1
    assert fe.health().healthy
    # dispatch accounting still holds under chaos: one query per SERVED
    # block, whichever engine ran it (failed primary attempts never
    # reached the executable)
    assert flows.DISPATCH["query_calls"] == fe.stats.blocks == 7
    _assert_no_stranded(futs + futs2)
    fe.close()
    n = len(futs) + len(futs2)
    emit(
        emit_name or f"chaos_breaker_trip_recover_{model}",
        wall / n * 1e6,
        f"trips=1;recoveries=1;fallback_blocks=5;failed=0"
        f";parity=bit_exact_both_flows{mesh_note}",
    )


def scenario_deadline_storm(model, task, sess):
    full = np.asarray(sess(task.params))
    plan = FaultPlan()
    plan.delay("dispatch", 0.02, times=1)  # one slow block, virtual time
    clock = FakeClock()
    fe = _frontend(sess, task.params, clock, faults=plan)
    t0 = time.perf_counter()
    # a saturated undeadlined block + 3 deadlined stragglers (too few to
    # saturate their capacity bucket, so they wait in queue)
    targets, futs = _submit_blocks(fe, 4, 2, task.batch.num_targets, seed=3)
    stale = [fe.submit([i], timeout=0.015) for i in range(3)]
    assert fe.pump() == 1  # serves the block; the injected delay drags
    # the clock to t=0.02, past the stragglers' 0.015 deadlines
    assert clock.now() >= 0.02
    assert fe.pump(force=True) == 0  # next drain expires them, no forward
    wall = time.perf_counter() - t0
    for t, f in zip(targets, futs):
        assert np.array_equal(f.result(0), full[t])
    for f in stale:
        try:
            f.result(0)
        except DeadlineExceededError:
            pass
        else:
            raise AssertionError("expired request served past its deadline")
    assert fe.stats.expired == 3 and fe.stats.completed == 4
    _assert_no_stranded(futs + stale)
    fe.close()
    emit(
        f"chaos_deadline_storm_{model}", wall / len(futs) * 1e6,
        "expired=3;served=4;expiry=typed_at_drain;forwards_for_dead=0",
    )


def scenario_tenant_unpublish(model, task, sess):
    full = np.asarray(sess(task.params))
    plane = WeightPlane(task.params)
    plane.publish("a", task.params)
    plane.publish("b", task.params)
    plan = FaultPlan()
    # the race: "b" vanishes between submit and its block's checkout
    plan.call(
        "checkout", lambda ctx: ctx.frontend.plane.unpublish("b"),
        tenant="b", times=1, label="unpublish-race",
    )
    clock = FakeClock()
    fe = _frontend(sess, plane, clock, faults=plan)
    t0 = time.perf_counter()
    rng = np.random.default_rng(4)
    ta = [rng.integers(0, task.batch.num_targets, 2).tolist() for _ in range(4)]
    tb = [rng.integers(0, task.batch.num_targets, 2).tolist() for _ in range(4)]
    fa = [fe.submit(t, tenant="a") for t in ta]
    fb = [fe.submit(t, tenant="b") for t in tb]
    clock.advance(POLICY.flush_timeout)
    fe.pump(force=True)
    wall = time.perf_counter() - t0
    for t, f in zip(ta, fa):
        assert np.array_equal(f.result(0), full[t]), (
            f"{model}: healthy tenant caught in the blast radius"
        )
    for f in fb:
        try:
            f.result(0)
        except TenantUnpublishedError:
            pass
        else:
            raise AssertionError("unpublished tenant served")
    # blast radius was ONE block; the breaker never saw a flow failure
    assert fe.stats.failed == 4 and fe.breaker.consecutive_failures == 0
    # republish restores service with no recompilation
    fe.plane.publish("b", task.params)
    f2 = fe.submit(ta[0], tenant="b")
    clock.advance(POLICY.flush_timeout)
    fe.pump(force=True)
    assert np.array_equal(f2.result(0), full[ta[0]])
    _assert_no_stranded(fa + fb + [f2])
    fe.close()
    emit(
        f"chaos_tenant_unpublish_{model}", wall / len(fa) * 1e6,
        "blast_radius=1_block;breaker_charged=0;republish=serves",
    )


def scenario_queue_saturation(model, task, sess):
    full = np.asarray(sess(task.params))
    policy = BatchPolicy(capacities=(1, 4, 8), flush_timeout=0.01,
                         max_pending=8)
    clock = FakeClock()
    fe = _frontend(sess, task.params, clock, policy=policy)
    rng = np.random.default_rng(5)
    targets = [
        [int(rng.integers(0, task.batch.num_targets))] for _ in range(20)
    ]
    t0 = time.perf_counter()
    admitted, shed = [], 0
    for t in targets:
        try:
            admitted.append((t, fe.submit(t)))
        except QueueFullError:
            shed += 1
    assert fe.pump(force=True) == 1  # the 8 admitted pack one block
    wall = time.perf_counter() - t0
    assert shed == 12 and fe.stats.shed == 12, (shed, fe.stats.shed)
    assert len(admitted) == 8 and fe.stats.completed == 8
    for t, f in admitted:
        assert np.array_equal(f.result(0), full[t]), (
            f"{model}: admitted request lost bit-exactness under shedding"
        )
    _assert_no_stranded([f for _, f in admitted])
    fe.close()
    emit(
        f"chaos_queue_saturation_{model}", wall / len(admitted) * 1e6,
        "submitted=20;admitted=8;shed=12;shed_mode=fast_typed"
        ";parity=bit_exact",
    )


def bench_model(model: str, scale: float):
    task = pipeline.prepare(model, "imdb", scale=scale, max_degree=32, seed=0)
    sess = task.compile(FlowConfig("fused_kernel", prune_k=PRUNE_K))
    # the degradation target: the plain-fused flow, whole capacity ladder
    # pre-compiled so a breaker trip mid-incident never compiles
    fb_sess = task.compile(
        FlowConfig("fused", prune_k=PRUNE_K)
    ).prewarm(POLICY.capacities)

    scenario_transient_retry(model, task, sess, None)
    scenario_breaker_trip_recover(model, task, sess, fb_sess)
    scenario_deadline_storm(model, task, sess)
    scenario_tenant_unpublish(model, task, sess)
    scenario_queue_saturation(model, task, sess)


def bench_sharded(model: str, scale: float):
    """Trip → degrade → recover with BOTH sessions 8-way mesh-sharded:
    the breaker swaps executables, never meshes, and parity holds against
    each flow's own sharded full forward."""
    task = pipeline.prepare(model, "imdb", scale=scale, max_degree=32, seed=0)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:8]), ("data",))
    with jax.set_mesh(mesh):
        sess = task.compile(FlowConfig("fused_kernel", prune_k=PRUNE_K))
        fb_sess = task.compile(
            FlowConfig("fused", prune_k=PRUNE_K)
        ).prewarm(POLICY.capacities)
        assert sess.mesh_info is not None and sess.mesh_info[2] == 8
        scenario_breaker_trip_recover(
            model, task, sess, fb_sess,
            emit_name=f"chaos_sharded_breaker_{model}",
            mesh_note=";mesh=8way",
        )


def main(smoke: bool = False, sharded: bool = False):
    scale = 0.04
    for model in ["rgat"] if smoke else ["rgat", "han"]:
        bench_model(model, scale)
    if len(jax.devices()) >= 8:
        bench_sharded("rgat", scale)
    elif sharded:
        raise SystemExit(
            "--sharded needs >= 8 devices "
            "(XLA_FLAGS=--xla_force_host_platform_device_count=8)"
        )
    else:
        print("(single-device runtime: sharded chaos row skipped)")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--smoke", action="store_true",
        help="one model, every fault class, all asserts — the CI "
        "fault-tolerance regression gate",
    )
    ap.add_argument(
        "--sharded", action="store_true",
        help="fail instead of skipping when < 8 devices are available "
        "(the CI multidevice job sets this)",
    )
    main(**vars(ap.parse_args()))
