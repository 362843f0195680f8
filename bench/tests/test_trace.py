"""The trace reduction (idle share, per-kernel time, gap attribution) on a
small recorded trace."""
import json
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).parent / "data"


def _planes():
    # window 1000..2000 ns; on the chip: ops at 900-1100 (half outside),
    # 1200-1300 (K1), 1250-1400 (overlaps: union), 1500-1600 (K2)
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ["bench.window", 1000, 1000, {}],
        ["bench.forward", 1050, 400, {}],
        ["bench.await", 1420, 300, {}],
    ]}]}
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_fn", 900, 800, {}]]},
        {"name": "XLA Ops", "events": [
            ["fusion.1", 900, 200, {"long_name": "fusion"}],
            ["custom-call.2", 1200, 100, {"long_name": "x = tpu_custom_call(_grouped_prune_kernel)"}],
            ["copy.3", 1250, 150, {}],
            ["custom-call.4", 1500, 100, {"long_name": "y = tpu_custom_call(_aggregate_kernel)"}],
        ]},
    ]}
    return [host, dev]


def test_busy_and_idle_are_clipped_to_the_window():
    tr = trace.Trace(_planes())
    assert tr.window_s == pytest.approx(1000e-9)
    # union inside the window: 1000-1100, 1200-1400, 1500-1600 = 400 ns
    assert tr.busy_s == pytest.approx(400e-9)
    assert tr.idle_gaps() == [(1100, 1200), (1400, 1500), (1600, 2000)]


def test_kernel_time_matches_name_or_stats():
    tr = trace.Trace(_planes())
    assert tr.op_seconds(r"grouped_prune_kernel") == pytest.approx(100e-9)
    assert tr.op_seconds(r"_aggregate_kernel") == pytest.approx(100e-9)
    assert tr.op_seconds(r"^fusion") == pytest.approx(100e-9)  # clipped
    assert tr.top_ops(2)[0] == ["copy.3", pytest.approx(150e-9)]


def test_gaps_are_labelled_by_the_innermost_host_span():
    tr = trace.Trace(_planes())
    got = dict((k, v) for k, v in tr.idle_by_span())
    # 1100-1200 lies in bench.forward; the midpoint of 1400-1500 (1450)
    # is where bench.forward ends and bench.await is open; 1600-2000
    # (midpoint 1800) lies in no span
    assert got == {"bench.forward": pytest.approx(100e-9),
                   "bench.await": pytest.approx(100e-9),
                   "no bench span": pytest.approx(400e-9)}


def test_no_window_span_is_an_error():
    planes = _planes()
    planes[0]["lines"][0]["events"].pop(0)
    with pytest.raises(ValueError):
        trace.Trace(planes)


def _pattern(metric):
    from bench import run

    return run.load_module(DATA.parents[1] / "metrics" / f"{metric}.py").PATTERN


@pytest.mark.parametrize("path", sorted(DATA.glob("*.excerpt.json")), ids=lambda p: p.name)
def test_recorded_chip_excerpt(path):
    """An excerpt of a trace recorded on a TPU v5e (the first 200 events of
    each line): the window, the chip's busy time and both kernels are
    found, and the kernels' ops are told apart."""
    planes = json.loads(path.read_text())
    tr = trace.Trace(planes)
    assert tr.devices and 0 < tr.busy_s <= tr.window_s
    k1 = tr.op_seconds(_pattern("k1_roofline.full"))
    k2 = tr.op_seconds(_pattern("k2_roofline.full"))
    assert k1 > 0 and k2 > 0
    both = tr.op_seconds(r"^%fused_prune_aggregate_grouped_pallas[.\d]* = ")
    assert k1 + k2 == pytest.approx(both)
