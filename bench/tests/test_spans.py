"""The readers of the program's spans and stage scopes (``bench/spans.py``
and the five metrics on it) on a small made-up trace and on excerpts
recorded on a TPU v5e, and a traced CPU rehearsal of ``han-dblp.serve``
that reads the four serving metrics."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import run, spans, trace

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).parent / "data"
SERVE = ("queue_wait_ms.serve", "pipe_wait_ms.serve", "service_ms.serve",
         "block_requests.serve")

# the forward's instructions and their op_name, as repro.tracing records them
SCOPES = {"jit_fn": {
    "fusion.1": "jit(fn)/jit(main)/fp/dot_general",
    "copy.2": "jit(fn)/jit(main)/na.APA/jit(_grouped_call)/transpose",
    "fpa.3": "jit(fn)/jit(main)/na.APA/jit(_grouped_call)/k1/pallas_call",
    "fpa.4": "jit(fn)/jit(main)/na.APA/jit(_grouped_call)/k2/pallas_call",
    "gather.5": "jit(fn)/jit(main)/na.APA/jit(_grouped_call)/gather",
    "fusion.6": "jit(fn)/jit(main)/fusion/dot_general",
}, "jit__gather": {"fusion.1": "jit(_gather)/jit(main)/gather/gather"}}


def _planes():
    # window 1000..3000 ns. Host: a drain on the collector, the stepper's
    # spans of blocks 7 and 8 (block 6's complete and block 9's dispatch
    # lie outside the window), a query dispatch. Device: one forward
    # module 1000-2000 and one gather module 2100-2200 whose op shares an
    # instruction name with the forward.
    host = {"name": "/host:CPU", "lines": [
        {"name": "python", "events": [
            ["bench.window", 1000, 2000, {}],
            ["bench.submit", 1010, 5, {}],
            ["$frontend.py:300 submit", 1011, 3, {}],
        ]},
        {"name": "serve-collector", "events": [
            ["serve.drain", 1020, 30, {"blocks": 2, "requests": 5}],
            ["serve.pipe_put", 1060, 900, {"block": 8}],
        ]},
        {"name": "serve-stepper", "events": [
            ["serve.complete", 900, 20, {"block": 6, "requests": 9, "service_us": 1e6}],
            ["serve.dispatch", 1100, 50, {
                "block": 7, "capacity": 4, "n_valid": 3, "requests": 2,
                "queue_wait_us_sum": 3000.0, "pipe_wait_us_sum": 8000.0}],
            ["session.query", 1110, 30, {"capacity": 4}],
            ["serve.dispatch", 1950, 40, {
                "block": 8, "capacity": 8, "n_valid": 6, "requests": 3,
                "queue_wait_us_sum": 3000.0, "pipe_wait_us_sum": 30000.0}],
            ["serve.sync", 2000, 90, {"block": 7}],
            ["serve.complete", 2100, 10, {"block": 7, "requests": 2, "service_us": 60000.0}],
            ["serve.complete", 2900, 10, {"block": 8, "requests": 3, "service_us": 70000.0}],
            ["serve.dispatch", 3100, 40, {"block": 9, "requests": 4,
                                          "queue_wait_us_sum": 1.0, "pipe_wait_us_sum": 1.0}],
        ]},
    ]}
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit_fn(123)", 1000, 1000, {}], ["jit__gather(456)", 2100, 100, {}]]},
        {"name": "XLA Ops", "events": [
            ["%fusion.1 = f32[8] fusion(%p.1)", 1000, 100, {}],
            ["%copy.2 = f32[8] copy(%fusion.1)", 1100, 50, {}],
            ["%fpa.3 = (f32[8], s32[8]) custom-call(%copy.2)", 1150, 400, {}],
            ["%gather.5 = s32[8] gather(%fpa.3)", 1550, 20, {}],
            ["%fpa.4 = f32[8] custom-call(%gather.5)", 1570, 300, {}],
            ["%fusion.6 = f32[8] fusion(%fpa.4)", 1870, 30, {}],
            ["%copy-done = f32[8] copy-done(%copy-start)", 1900, 10, {}],
            ["%fusion.1 = f32[4] fusion(%p.1)", 2120, 20, {}],
        ]},
    ]}
    return [host, dev]


@pytest.fixture
def ctx(monkeypatch):
    from repro import tracing

    monkeypatch.setattr(tracing, "_SCOPES", SCOPES)
    return SimpleNamespace(trace=trace.Trace(_planes()), run={"forwards": 1})


def _read(metric, ctx):
    return run.load_module(ROOT / "bench" / "metrics" / f"{metric}.py").read(ctx)


def test_host_spans_are_the_in_window_ones_with_their_stats(ctx):
    got = spans.host_spans(ctx.trace, "serve.dispatch")
    assert [s[2]["block"] for s in got] == [7, 8]
    assert [s[2]["block"] for s in spans.host_spans(ctx.trace, "serve.complete")] == [7, 8]


@pytest.mark.parametrize("metric, want", [
    ("queue_wait_ms.serve", (3000.0 + 3000.0) / 5 * 1e-3),
    ("pipe_wait_ms.serve", (8000.0 + 30000.0) / 5 * 1e-3),
    ("service_ms.serve", (2 * 60000.0 + 3 * 70000.0) / 5 * 1e-3),
    ("block_requests.serve", 5 / 2),
    ("na_glue_ms.full", (50 + 20) * 1e-9 * 1e3),
])
def test_reader(ctx, metric, want):
    assert _read(metric, ctx) == pytest.approx(want)


def test_device_time_by_stage_uses_each_op_module(ctx):
    got = spans.seconds_by_stage(ctx.trace)
    assert got == pytest.approx({
        "fp": 100e-9, "na.APA/glue": 70e-9, "na.APA/k1": 400e-9,
        "na.APA/k2": 300e-9, "fusion": 30e-9, "unscoped": 10e-9,
        "gather": 20e-9,  # the gather module's fusion.1, not the forward's
    })


def test_stage_names():
    assert spans.stage(None) == "unscoped"
    assert spans.stage("jit(fn)/na.P-A/jit(x)/k2/pallas_call") == "na.P-A/k2"
    assert spans.stage("jit(fn)/na.P-A/gather") == "na.P-A/glue"
    assert spans.stage("jit(fn)/fusion/add") == "fusion"
    assert spans.stage("jit(fn)/add") == "unscoped"
    assert spans.stage("jit(fn)/gather") == "unscoped"  # the primitive
    assert spans.stage("jit(_gather)/gather/gather") == "gather"


def test_gaps_are_labelled_by_program_spans(ctx):
    got = dict((k, v) for k, v in spans.idle_by_span(ctx.trace))
    # device idle 1910-2120 (midpoint 2015, inside serve.sync) and
    # 2140-3000 (midpoint 2570, inside no span)
    assert got == {"serve.sync": pytest.approx(210e-9),
                   "no bench span": pytest.approx(860e-9)}
    # the harness's own labelling sees bench.* spans only
    assert dict(ctx.trace.idle_by_span()) == {"no bench span": pytest.approx(1070e-9)}


def test_an_older_program_reads_nothing(monkeypatch):
    import sys

    planes = _planes()
    planes[0]["lines"] = planes[0]["lines"][:1]  # no serve.* or session.* span
    import repro

    # no seam to import
    monkeypatch.delattr(repro, "tracing", raising=False)
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    old = SimpleNamespace(trace=trace.Trace(planes), run={"forwards": 1})
    for metric in SERVE + ("na_glue_ms.full",):
        assert _read(metric, old) is None


# na_glue_ms.full over a whole traced window on the chip (TPU v5 lite, 30 s)
WINDOW_GLUE_MS = {"han-dblp.full": 4.628543104722793,
                  "simplehgn-acm.full": 9.911306502092051}


@pytest.mark.parametrize("cell", sorted(WINDOW_GLUE_MS))
def test_recorded_forward_by_stage(cell, monkeypatch):
    """One forward recorded on a TPU v5e, with the op_name table the
    program kept for it: all but a few async copies carry a scope, the
    scopes' k1 and k2 totals are what the K1/K2 name patterns find, and
    this forward's glue reads within 2% of its window's per-forward value."""
    from repro import tracing

    data = json.loads((DATA / f"{cell}.scopes.json").read_text())
    monkeypatch.setattr(tracing, "_SCOPES", data["scopes"])
    tr = trace.Trace(data["planes"])
    got = spans.seconds_by_stage(tr)
    assert got.get("unscoped", 0.0) < 0.01 * sum(got.values())
    for k in ("k1", "k2"):
        pattern = run.load_module(ROOT / "bench" / "metrics" / f"{k}_roofline.full.py").PATTERN
        by_scope = sum(v for s, v in got.items() if s.endswith(f"/{k}"))
        assert by_scope == pytest.approx(tr.op_seconds(pattern))
    glue = _read("na_glue_ms.full", SimpleNamespace(trace=tr, run={"forwards": 1}))
    assert glue == pytest.approx(WINDOW_GLUE_MS[cell], rel=0.02)


def test_recorded_serving_split():
    """Three seconds of han-dblp.serve recorded on a TPU v5e: the readers
    give what they gave on the chip, and a block, one forward of about
    60 ms, is served within two forwards of its dispatch."""
    tr = trace.Trace(json.loads((DATA / "han-dblp.serve.spans.json").read_text())["planes"])
    got = {m: _read(m, SimpleNamespace(trace=tr, run={})) for m in SERVE}
    assert got == pytest.approx({
        "queue_wait_ms.serve": 53.387501467333266,
        "pipe_wait_ms.serve": 192.98047663819463,
        "service_ms.serve": 119.95804939691057,
        "block_requests.serve": 3.9019607843137254,
    })
    assert 60 < got["service_ms.serve"] < 2 * 62


def test_traced_rehearsal_reads_the_serving_split():
    out = run.run_cell(ROOT, "han-dblp.serve", 3000000007, 1.0, True,
                       rehearse=True, scale=0.02, log=lambda s: None)
    assert out["correct"] is True, out["checks"]
    got = {k: v["value"] for k, v in out["rehearsal"]["metrics"].items()}
    for m in ("queue_wait_ms.serve", "pipe_wait_ms.serve", "service_ms.serve",
              "block_requests.serve"):
        assert got[m] >= 0, (m, got)
    assert 1 <= got["block_requests.serve"] <= 16
