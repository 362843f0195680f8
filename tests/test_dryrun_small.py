"""Small-mesh dry-run integration tests (subprocess: device count is locked
at first jax init, so mesh tests get their own interpreter)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

pytestmark = pytest.mark.slow

ROOT = Path(__file__).resolve().parent.parent


def _run(args, devices=8):
    env = {
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
        "PYTHONPATH": str(ROOT / "src"),
        "PATH": "/usr/bin:/bin",
    }
    # the child runs on the parent's platform (the suite runs on the CPU)
    # and keeps the parent's home and scratch directories
    for name in ("JAX_PLATFORMS", "HOME", "TMPDIR"):
        if name in os.environ:
            env[name] = os.environ[name]
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun"] + args,
        capture_output=True, text=True, env=env, timeout=900, cwd=str(ROOT),
    )


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "olmoe-1b-7b", "rwkv6-3b"])
def test_smoke_dryrun_single_mesh(arch, tmp_path):
    r = _run([
        "--arch", arch, "--shape", "train_4k", "--mesh", "single",
        "--smoke", "--mesh-shape", "2,4", "--mesh-axes", "data,model",
        "--out", str(tmp_path), "--no-probe",
    ])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    recs = list(tmp_path.glob("*.json"))
    assert recs
    rec = json.loads(recs[0].read_text())
    assert rec["status"] == "ok"


def test_smoke_dryrun_multi_pod_mesh(tmp_path):
    r = _run([
        "--arch", "gemma3-4b", "--shape", "decode_32k", "--mesh", "multi",
        "--smoke", "--mesh-shape", "2,2,2", "--mesh-axes", "pod,data,model",
        "--out", str(tmp_path), "--no-probe",
    ])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    rec = json.loads(next(iter(tmp_path.glob("*.json"))).read_text())
    assert rec["status"] == "ok"
    assert rec["chips"] == 8


def test_smoke_dryrun_probe_extrapolation(tmp_path):
    r = _run([
        "--arch", "qwen2-1.5b", "--shape", "train_4k", "--mesh", "single",
        "--smoke", "--mesh-shape", "2,4", "--mesh-axes", "data,model",
        "--out", str(tmp_path),
    ])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    rec = json.loads(next(iter(tmp_path.glob("*.json"))).read_text())
    assert rec["status"] == "ok"
    assert "probe_d1" in rec["probe"], rec["probe"]
    # extrapolated flops exceed the single-visit scanned count
    assert rec["flops_per_device"] > rec["scanned_cost"].get("flops", 0) * 0.9
