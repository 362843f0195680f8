"""The tracing seam (``repro.tracing``) and the spans and scopes the program
writes through it.

* While no profiler trace runs, ``span`` touches nothing in
  ``jax.profiler``; under a CPU trace, a span and its stats come back as a
  host event through ``bench.trace.load``.
* ``ServeFrontend`` on ``FakeClock`` + ``InlineExecutor``: the
  ``serve.dispatch`` stats carry exact queue- and pipe-wait sums, and per
  request queue wait + pipe wait + service is the ``ServeStats`` latency
  exactly; each block's spans share one ``block`` id, threaded too.
* The compiled forward's instructions carry the stage scopes.
"""
import jax
import numpy as np
import pytest

from bench import trace as bench_trace
from repro import tracing
from repro.core import pipeline
from repro.core.flows import FlowConfig
from repro.serve import (
    BatchPolicy,
    FakeClock,
    InlineExecutor,
    ServeFrontend,
    SystemClock,
    ThreadExecutor,
)


def _events(trace_dir, prefix):
    """Host events ``[name, start_ns, dur_ns, stats]`` named ``prefix*``."""
    return sorted(
        (ev for p in bench_trace.load(str(trace_dir))
         if not bench_trace.is_device_plane(p["name"])
         for ln in p["lines"] for ev in ln["events"]
         if ev[0].startswith(prefix)),
        key=lambda ev: ev[1],
    )


def test_span_off_calls_nothing_in_the_profiler(monkeypatch):
    def refuse(*a, **kw):
        raise AssertionError("jax.profiler called while tracing is off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    assert not tracing.enabled()
    with tracing.span("serve.test", block=1) as sp:
        sp.set_metadata(requests=2)


def test_span_on_lands_in_the_profiler_trace(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        assert tracing.enabled()
        with tracing.span("serve.test", block=3, wait_us=1.5) as sp:
            sp.set_metadata(requests=2, tag="a/b")
    assert not tracing.enabled()
    (ev,) = _events(tmp_path, "serve.test")
    assert ev[3] == {"block": 3, "wait_us": 1.5, "requests": 2, "tag": "a/b"}


class SlowSession:
    """Each query advances the fake clock by ``step`` seconds: the device
    time of a block, so the stepper's double buffering shows as pipe wait
    and service."""

    donate_params = False

    def __init__(self, clock, step=1.0):
        self.clock, self.step = clock, step
        self.table = np.arange(64 * 3, dtype=np.float32).reshape(64, 3)

    def compile_query(self, capacity):
        pass

    def query(self, params, idx):
        self.clock.advance(self.step)
        return self.table[np.asarray(idx)]


def _traced_inline_run(tmp_path):
    """Requests A(2 ids, t=0), B(1, t=1) packed at t=3 into block 0;
    C(4), D(4), E(2) at t=5, packed at t=7 into block 1 (C, D: saturated)
    and block 2 (E). Block 1 dispatches at 7, block 2 at 8 (pipe wait 1);
    both resolve at 9."""
    clock = FakeClock()
    policy = BatchPolicy(capacities=(1, 4, 8), flush_timeout=2.0)
    fe = ServeFrontend(
        SlowSession(clock), {"w": np.float32(1.0)}, policy=policy,
        clock=clock, executor=InlineExecutor(),
    )
    with jax.profiler.trace(str(tmp_path)):
        fe.submit([1, 2])
        clock.advance(1.0)
        fe.submit([3])
        clock.advance(2.0)
        assert fe.pump() == 1
        clock.advance(1.0)
        for ids in ([4, 5, 6, 7], [8, 9, 10, 11], [12, 13]):
            fe.submit(ids)
        clock.advance(2.0)
        assert fe.pump() == 2
    return fe


def test_dispatch_spans_carry_exact_wait_sums(tmp_path):
    _traced_inline_run(tmp_path)
    got = {ev[3]["block"]: ev[3] for ev in _events(tmp_path, "serve.dispatch")}
    assert got == {
        0: {"block": 0, "capacity": 4, "n_valid": 3, "requests": 2,
            "queue_wait_us_sum": 5e6, "pipe_wait_us_sum": 0.0},
        1: {"block": 1, "capacity": 8, "n_valid": 8, "requests": 2,
            "queue_wait_us_sum": 4e6, "pipe_wait_us_sum": 0.0},
        2: {"block": 2, "capacity": 4, "n_valid": 2, "requests": 1,
            "queue_wait_us_sum": 2e6, "pipe_wait_us_sum": 1e6},
    }
    done = {ev[3]["block"]: ev[3] for ev in _events(tmp_path, "serve.complete")}
    assert {b: (s["requests"], s["service_us"]) for b, s in done.items()} == {
        0: (2, 1e6), 1: (2, 2e6), 2: (1, 1e6)}
    drains = [ev[3] for ev in _events(tmp_path, "serve.drain") if ev[3]]
    assert drains == [{"blocks": 1, "requests": 2}, {"blocks": 2, "requests": 3}]


def test_waits_and_service_sum_to_the_latency(tmp_path):
    fe = _traced_inline_run(tmp_path)
    dispatch = {ev[3]["block"]: ev[3] for ev in _events(tmp_path, "serve.dispatch")}
    done = {ev[3]["block"]: ev[3] for ev in _events(tmp_path, "serve.complete")}
    # ServeStats appends each block's latencies in completion order
    lat = iter(fe.stats.latencies)
    for b in sorted(done):
        d, c = dispatch[b], done[b]
        block_lat = [next(lat) for _ in range(c["requests"])]
        split = d["queue_wait_us_sum"] + d["pipe_wait_us_sum"] + (
            c["requests"] * c["service_us"])
        assert split == sum(block_lat) * 1e6
    assert next(lat, None) is None
    assert fe.stats.latencies == [4.0, 3.0, 4.0, 4.0, 4.0]
    assert fe.stats.summary()["mean_batch"] == 13 / 3


def test_each_block_shares_one_id_across_its_spans(tmp_path):
    _traced_inline_run(tmp_path)
    by_block = {}
    for ev in _events(tmp_path, "serve."):
        if "block" in ev[3]:
            by_block.setdefault(ev[3]["block"], []).append(ev[0])
    assert by_block == {
        b: ["serve.dispatch", "serve.sync", "serve.complete"] for b in (0, 1, 2)}
    assert len(_events(tmp_path, "serve.submit")) == 5


def test_threaded_blocks_carry_their_id_from_pipe_to_completion(tmp_path):
    fe = ServeFrontend(
        SlowSession(FakeClock(), step=0.0), {"w": np.float32(1.0)},
        policy=BatchPolicy(capacities=(1, 4, 8), flush_timeout=1e-3),
        clock=SystemClock(), executor=ThreadExecutor(),
    )
    with jax.profiler.trace(str(tmp_path)):
        with fe:
            futs = [fe.submit([i, i + 1]) for i in range(12)]
            for f in futs:
                f.result(timeout=30)
    by_block = {}
    for ev in _events(tmp_path, "serve."):
        if "block" in ev[3]:
            by_block.setdefault(ev[3]["block"], set()).add(ev[0])
    assert by_block and sorted(by_block) == list(range(len(by_block)))
    assert all(names == {"serve.pipe_put", "serve.dispatch", "serve.sync",
                         "serve.complete"} for names in by_block.values())
    served = sum(ev[3]["requests"] for ev in _events(tmp_path, "serve.complete"))
    assert served == 12 == fe.stats.completed


def test_compiled_forward_carries_the_stage_scopes():
    task = pipeline.prepare("han", "dblp", scale=0.02, max_degree=32, seed=0)
    sess = task.compile(FlowConfig("fused_kernel", prune_k=4))
    sess.compile_query(4)
    paths = [p for table in tracing.op_scopes().values() for p in table.values()]
    parts = {c for p in paths for c in p.split("/")}
    assert {"fp", "fusion", "gather", "k1", "k2"} <= parts
    # each semantic graph's kernel pair nests inside its na.<graph> scope
    split = [p.split("/") for p in paths]
    for sg in task.sgs:
        na = f"na.{sg.name}"
        for k in ("k1", "k2"):
            assert any(na in c and k in c[c.index(na):] for c in split)


@pytest.mark.parametrize("model", ["han", "simple_hgn"])
def test_compiled_forward_counts_its_k1_steps(model):
    """The forward program keeps the K1 grid steps its NA call sites
    launch, each semantic graph's grouped layout at its own tile shape;
    a call site XLA drops (a last layer's NA into a type no logit reads)
    counts nothing."""
    from repro.kernels.fused_prune_aggregate.ops import tile_shape

    task = pipeline.prepare(model, "acm", scale=0.02, max_degree=32, seed=0)
    before = dict(tracing.program_counts())
    sess = task.compile(FlowConfig("fused_kernel", prune_k=4))
    new = {m: c for m, c in tracing.program_counts().items() if before.get(m) is not c}
    (counts,) = new.values()
    steps = {sg.name: sg.grouped(*tile_shape(sg)).num_steps for sg in task.sgs}
    layers = getattr(task.model, "num_layers", 1)
    label = [sg.name for sg in task.sgs if sg.dst_type == task.graph.label_type]
    want = sum(steps.values()) + (layers - 1) * sum(steps[n] for n in label)
    assert counts == {"k1_steps": want} and want > 0
    with tracing.counting() as outside:
        sess(task.params)  # dispatching a compiled program counts nothing
    assert outside == []


@pytest.mark.parametrize("name", ["session.forward", "session.query"])
def test_session_dispatches_are_spans(tmp_path, name):
    task = pipeline.prepare("rgat", "imdb", scale=0.04, max_degree=32, seed=0)
    sess = task.compile(FlowConfig("fused", prune_k=8))
    idx = np.arange(4, dtype=np.int32)
    sess.compile_query(4)
    with jax.profiler.trace(str(tmp_path)):
        if name == "session.forward":
            jax.block_until_ready(sess(task.params))
        else:
            jax.block_until_ready(sess.query(task.params, idx))
    (ev,) = _events(tmp_path, name)
    if name == "session.query":
        assert ev[3] == {"capacity": 4}
