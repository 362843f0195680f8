"""A model, a configuration, a traffic mix and a per-layer metric added as
files alone, with an entry in BENCHMARK.json, are found and run: the
harness needs no edit for a new cell."""
import json
import shutil
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture
def tree(tmp_path):
    """A checkout holding the benchmark, the program, and new files: a
    model (its adapter and its reference, here HAN's under another name),
    a configuration of it, a mix and a metric."""
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns(".cache", "tests", "__pycache__"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    for part in ("models", "reference"):
        shutil.copy(bench / part / "han.py", bench / part / "hanlike.py")
    cfg = json.loads((ROOT / "bench" / "configs" / "han-dblp.json").read_text())
    cfg["name"] = "tiny-han"
    cfg["model"] = "hanlike"
    cfg["metapaths"] = {"APA": ["AP", "AP_rev"]}
    cfg["graph"]["data_seed"] = 99
    (tmp_path / "bench" / "configs" / "tiny-han.json").write_text(json.dumps(cfg))
    (tmp_path / "bench" / "traffic" / "trickle.json").write_text(json.dumps({
        "kind": "open", "rate_rps": 20.0,
        "arrivals": {"process": "onoff", "on_s": 0.25, "off_s": 0.25},
        "ids": {"dist": "zipf", "exponent": 1.2, "rotate_s": 0.5},
        "ids_per_request": [2, 3],
        "capacities": [4, 8], "flush_timeout_s": 0.002, "schedule_seed": 1,
        "drain_s": 30,
    }))
    (tmp_path / "bench" / "metrics" / "answered.trickle.py").write_text(
        "def read(ctx):\n    return ctx.run['attempted'] - ctx.run['failed']\n")
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "tiny-han", "source": "https://arxiv.org/abs/1903.07293",
                          "file": "bench/configs/tiny-han.json", "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "tiny-han.trickle", "config": "tiny-han",
                            "traffic": "trickle", "chips": 1, "why": "test"})
    for m in bm["end_to_end"]:
        if m["name"] == "p95_ms":
            m["workloads"].append("tiny-han.trickle")
    bm["per_layer"].append({"name": "answered.trickle", "unit": "req", "better": "higher",
                            "source": "program_counter", "layer": "serve", "moves": "p95_ms",
                            "workloads": ["tiny-han.trickle"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    return tmp_path


@pytest.mark.parametrize("trace", [False, True], ids=["end_to_end", "per_layer"])
def test_new_files_are_found_and_run(tree, trace):
    from bench import run

    out = run.run_cell(tree, "tiny-han.trickle", 3, 1.0, trace, rehearse=True,
                       scale=0.02, log=lambda s: None)
    assert out["correct"] is True, out["checks"]
    got = out["rehearsal"]["metrics"]
    if trace:
        assert got["answered.trickle"]["value"] == out["attempted"] == 20
        assert "idle_share.serve" not in got  # another cell's metric
    else:
        assert set(got) == {"p95_ms", "setup_s"}
