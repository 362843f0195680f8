"""Find a serving cell's knee once, by a sweep of open-loop rates on the chip.

    python3 bench/sweep.py --workload han-dblp.serve --rates 40,80,120 --seconds 8

One process builds the cell's session once and runs the cell's open-loop
mix at each rate in turn. Per rate it prints the p50 and p95 latency (from
the due time), the growth of the backlog (median latency of the last fifth
of requests over that of the first fifth) and how late the generator ran.
The knee is the highest rate whose backlog does not grow (growth under
``--growth``) and whose p95 meets ``--p95-limit-ms``. The cell runs at a
fixed rate written into its mix file; this tool only informs that choice.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import graph, load, run  # noqa: E402
from bench.window import Window  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--p95-limit-ms", type=float, default=float("inf"))
    ap.add_argument("--growth", type=float, default=1.5)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--scale", type=float, default=1.0)
    a = ap.parse_args(argv)
    import jax

    _, cell, cfg, mix, _, _ = run.find_cell(ROOT, a.workload)
    assert mix["kind"] == "open", "the sweep is for open-loop cells"
    run.check_devices(int(cell["chips"]), a.rehearse)
    if not a.rehearse:
        from repro import compile_cache

        compile_cache.enable()
    g = graph.make_graph(cfg["graph"], scale=a.scale)
    adapter = run.load_module(ROOT / "bench" / "models" / f"{cfg['model']}.py")
    task = run.program_task(cfg, g, adapter, ROOT / "bench" / ".cache" / "sgb")
    params = run.init_params(a.seed, adapter.param_shapes(cfg, g, [s.name for s in task.sgs]))
    from repro.core.flows import FlowConfig

    with jax.default_matmul_precision(cfg["precision"]):
        session = task.compile(FlowConfig(cfg["flow"], prune_k=cfg["prune_k"]), params=params)
    n_targets = g["num_nodes"][g["label_type"]]
    knee = None
    for i, rate in enumerate(float(r) for r in a.rates.split(",")):
        m = dict(mix, rate_rps=rate)
        t = time.perf_counter()
        res = load.open_loop(session, params, m, a.seed + i, a.seconds, n_targets, Window())
        lat = np.asarray(res["latency_s"]) * 1e3
        fifth = max(1, lat.size // 5)
        growth = float(np.median(lat[-fifth:]) / np.median(lat[:fifth]))
        p95 = res["p95_ms"]
        ok = growth < a.growth and p95 <= a.p95_limit_ms and res["failed"] == 0
        if ok:
            knee = rate
        print(json.dumps({
            "rate_rps": rate, "requests": res["attempted"], "failed": res["failed"],
            "p50_ms": float(np.median(lat)), "p95_ms": p95, "growth": growth,
            "blocks": res["blocks"], "backlog_at_end": res["backlog_at_end"],
            "generator_late_ms": res["generator_late_ms"], "sustained": ok,
            "wall_s": time.perf_counter() - t,
        }), flush=True)
    print(json.dumps({"knee_rps": knee}), flush=True)


if __name__ == "__main__":
    main()
