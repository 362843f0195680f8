"""K1's cost per grid step and the NA glue's per padded slot, by tile shape.

    python benchmarks/k1_tile_sweep.py [--widths 8,16,32,64,128] [--t-tiles 8]

On one TPU chip: for every semantic graph of the benchmark's
configurations (``bench/configs``, their graphs at full scale), one
grouped NA launch per tile shape ``(t_tile, w)``, from random θ and
features, traced for ``--iters`` calls. Prints one JSON line per shape:
K1's grid steps and device ms a call, K2's, the NA glue's (the rest of the
call's device time) and the padded slots. ``ops.tile_shape``'s constants
``C_STEP_US`` and ``C_SLOT_US`` are read from these lines. ``--rehearse``
runs on any backend at ``--scale``; its times are not device times.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import graph  # noqa: E402
from bench import trace as trace_mod  # noqa: E402
from bench.run import load_module, program_task, read_json  # noqa: E402

K1 = r"^%fused_prune_aggregate_grouped_pallas[.\d]* = \("
K2 = r"^%fused_prune_aggregate_grouped_pallas[.\d]* = f32\["


def shape_row(sg, n_src, t_tile, w, prune_k, iters, rng, rel):
    from repro.kernels.fused_prune_aggregate import ops

    lay = sg.grouped(t_tile, w)
    (nbr, msk, ety, row_targets, perm), (meta, agg_meta, k_s) = ops._layout_device(
        lay, prune_k
    )
    h, dh = 8, 8
    args = (
        jnp.asarray(rng.normal(size=(n_src, h, dh)), jnp.float32),
        jnp.asarray(rng.normal(size=(n_src, h)), jnp.float32),
        jnp.asarray(rng.normal(size=(sg.num_targets, h)), jnp.float32),
        jnp.asarray(rng.normal(size=(max(sg.num_edge_types, 1), h)), jnp.float32)
        if rel else None,
        nbr, msk, ety, row_targets, meta, agg_meta, perm,
    )
    kw = dict(k_s=k_s, t_tile=t_tile, w=w, slope=0.2, use_rel=rel)
    jax.block_until_ready(ops._grouped_call(*args, **kw))
    trace_dir = tempfile.mkdtemp(prefix="k1-sweep-")
    try:
        jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
            t0 = time.perf_counter()
            for _ in range(iters):
                out = ops._grouped_call(*args, **kw)
            jax.block_until_ready(out)
            host_s = time.perf_counter() - t0
        jax.profiler.stop_trace()
        tr = trace_mod.Trace(trace_mod.load(trace_dir))
        k1, k2, busy = tr.op_seconds(K1), tr.op_seconds(K2), tr.busy_s
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    ms = lambda s: s * 1e3 / iters
    return {
        "graph": sg.name, "t_tile": t_tile, "w": w,
        "rule_w": ops.tile_shape(sg, t_tile)[1],
        "k1_steps": lay.num_steps, "slots": lay.num_steps * t_tile * w,
        "k2_steps": int(agg_meta.shape[1]),
        "k1_ms": ms(k1), "k2_ms": ms(k2), "glue_ms": ms(busy - k1 - k2),
        "host_ms": host_s * 1e3 / iters,
        "k1_us_per_step": k1 * 1e6 / iters / max(lay.num_steps, 1),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--configs", default="han-dblp,simplehgn-acm")
    ap.add_argument("--widths", default="8,16,32,64,128")
    ap.add_argument("--t-tiles", default="8")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--scale", type=float, default=1.0)
    a = ap.parse_args(argv)
    if not a.rehearse and jax.devices()[0].platform != "tpu":
        print("error: no TPU", file=sys.stderr)
        return 3
    widths = [int(x) for x in a.widths.split(",")]
    t_tiles = [int(x) for x in a.t_tiles.split(",")]
    rng = np.random.default_rng(0)
    with jax.default_matmul_precision("highest"), tempfile.TemporaryDirectory() as sgb:
        for name in a.configs.split(","):
            cfg = read_json(ROOT / "bench" / "configs" / f"{name}.json")
            g = graph.make_graph(cfg["graph"], scale=a.scale)
            adapter = load_module(ROOT / "bench" / "models" / f"{cfg['model']}.py")
            task = program_task(cfg, g, adapter, Path(sgb))
            n_src = sum(g["num_nodes"].values())
            rel = cfg["model"] != "han"
            for sg in task.sgs:
                for t_tile in t_tiles:
                    for w in widths:
                        row = shape_row(sg, n_src, t_tile, w, cfg["prune_k"],
                                        a.iters, rng, rel)
                        row["config"] = name
                        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
