"""The on-chip benchmark: one cell of ``BENCHMARK.json`` per run.

    python3 bench/run.py --workload han-dblp.full --seed 7 --seconds 10 --trace 0

Everything a cell needs is found by name: the cell's entry in
``BENCHMARK.json`` names a configuration (``bench/configs/<config>.json``,
with its program adapter ``bench/models/<model>.py``, which also counts
the model's work, and its plain reference ``bench/reference/<model>.py``)
and a traffic mix (``bench/traffic/<traffic>.json``, run by
``bench/load.py``); each per-layer metric is read by
``bench/metrics/<metric>.py``.

A run: generate the configuration's graph (fixed by its data seed), build
the program's task and the session the cell serves (``task.compile`` at
the configuration's precision), make the weights on the device from
``--seed``, warm every shape the mix uses, measure for ``--seconds``, read
the device's peak memory, free the program, then compute the reference and
compare every answer the window produced. ``--trace 1`` runs the same
window under the profiler and reports the per-layer metrics instead of the
end-to-end ones.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, optionally
``breakdown``, and ``checks`` last: each number compared with its limit);
the checks are also the last lines of standard error. With no TPU, or fewer
chips than the cell asks for, the run prints no result and exits 3.
``--rehearse`` runs on any backend at ``--scale`` (a CPU rehearsal) and
prints no device metrics.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import costs, graph, load  # noqa: E402
from bench import trace as trace_mod  # noqa: E402
from bench.window import Window  # noqa: E402

EXIT_NO_CHIP = 3
CACHE_MAX_BYTES = 8 << 30


class NoChip(RuntimeError):
    pass


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(f"bench_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(root: Path, workload: str):
    bm = read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = next(c for c in bm["configs"] if c["name"] == cell["config"])
    cfg = read_json(root / cfg_entry["file"])
    mix = read_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    applies = lambda m: workload in m.get("workloads", [workload])
    e2e = [m for m in bm["end_to_end"] if applies(m)]
    per_layer = [m for m in bm["per_layer"] if applies(m)]
    return bm, cell, cfg, mix, e2e, per_layer


def check_devices(chips: int, rehearse: bool):
    import jax

    devs = jax.devices()
    if rehearse:
        return devs
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


def init_params(seed: int, shapes):
    """The weights, on the device, in one jitted call from ``seed``:
    glorot-uniform matrices, uniform(-0.1, 0.1) vectors, float32."""
    import jax
    import jax.numpy as jnp

    is_shape = lambda x: isinstance(x, tuple) and all(isinstance(i, int) for i in x)
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=is_shape)

    def make(key):
        out = []
        for i, shp in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            lim = math.sqrt(6.0 / (shp[0] + math.prod(shp[1:]))) if len(shp) >= 2 else 0.1
            out.append(jax.random.uniform(k, shp, jnp.float32, -lim, lim))
        return jax.tree.unflatten(treedef, out)

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return jax.jit(make)(key)


def program_task(cfg: dict, g: dict, adapter, cache_dir: Path):
    """The program's task for the configuration: its model registered at
    the configuration's widths, over the benchmark's graph."""
    from repro.core import hetgraph, pipeline
    from repro.core.models import register_model

    name = f"bench:{cfg['name']}"
    register_model(name, adapter.factory(cfg), adapter.SGB_KIND)
    hg = hetgraph.HetGraph(
        node_types=g["node_types"], num_nodes=dict(g["num_nodes"]),
        features=g["features"], relations=g["relations"], edges=g["edges"],
        label_type=g["label_type"], labels=g["labels"],
        num_classes=g["num_classes"],
    )
    return pipeline.prepare(
        name, hg, max_degree=cfg["max_degree"], seed=cfg["graph"]["data_seed"],
        sgb_cache_dir=str(cache_dir), metapaths=adapter.metapaths(cfg),
    )


def compare(result: dict, lo: np.ndarray, hi: np.ndarray, left_out: np.ndarray):
    """Widest distance of a program logit outside the reference's envelope
    ``[lo, hi]``, over every answer of the window, as a share of the
    largest |reference logit|; rows the envelope cannot bound
    (``left_out``) are not compared. Returns ``(gap, answers compared)``."""
    scale = float(np.maximum(np.abs(lo), np.abs(hi)).max())
    keep = ~left_out

    def off(rows, ids):
        d = np.maximum(np.maximum(lo[ids] - rows, rows - hi[ids]), 0.0)[keep[ids]]
        return float(d.max()) if d.size else 0.0

    worst, n = 0.0, 0
    if "outputs" in result:
        every = np.arange(lo.shape[0])
        for out in result["outputs"]:
            out = np.asarray(out)
            if out.shape != lo.shape:
                return math.inf, n
            worst = max(worst, off(out, every))
            n += 1
    else:
        for ids, rows in result["rows"]:
            if rows is None:
                continue
            if rows.shape != (len(ids), lo.shape[1]):
                return math.inf, n
            worst = max(worst, off(rows, ids))
            n += 1
    return worst / scale, n


def memory_peak(devs) -> int:
    stats = devs[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def run_cell(root: Path, workload: str, seed: int, seconds: float, trace: bool,
             rehearse: bool = False, scale: float = 1.0, precision=None,
             t_start: float = T_START, log=print):
    """One run of one cell; returns the result record (``checks`` last).
    ``precision`` overrides the configuration's matmul precision (the
    control: a lower precision must come out not correct)."""
    import jax

    _, cell, cfg, mix, e2e, per_layer = find_cell(root, workload)
    devs = check_devices(int(cell["chips"]), rehearse)
    bench = root / "bench"
    if not rehearse:
        from repro import compile_cache

        compile_cache.enable()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        # the session's executable holds the graph's features and tables as
        # constants (0.7 GB for han-dblp): a smaller limit refuses its entry
        # and every process compiles it anew
        jax.config.update("jax_compilation_cache_max_size", CACHE_MAX_BYTES)

    g = graph.make_graph(cfg["graph"], scale=scale)
    log(f"[graph] {cfg['name']}: {graph.count_report(g, cfg['graph'])}")
    log(f"[setup] graph generated at {time.perf_counter() - t_start:.3f} s")
    adapter = load_module(bench / "models" / f"{cfg['model']}.py")
    task = program_task(cfg, g, adapter, bench / ".cache" / "sgb")
    log(f"[setup] program task prepared at {time.perf_counter() - t_start:.3f} s")
    shapes = adapter.param_shapes(cfg, g, [sg.name for sg in task.sgs])
    params = init_params(seed, shapes)
    if jax.tree.structure(params) != jax.tree.structure(task.params) or any(
        a.shape != b.shape for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(task.params))
    ):
        raise RuntimeError("the benchmark's weight tree does not match the model's")

    from repro.core.flows import FlowConfig

    prec = precision or cfg["precision"]
    with jax.default_matmul_precision(prec):
        session = task.compile(FlowConfig(cfg["flow"], prune_k=cfg["prune_k"]), params=params)
    num_targets = g["num_nodes"][g["label_type"]]
    log(f"[setup] session compiled at {time.perf_counter() - t_start:.3f} s ({prec})")

    # set-up's objects live to the end: keep the collector from walking
    # them again inside the window
    gc.collect()
    gc.freeze()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    window = Window(trace_dir)
    driver = load.DRIVERS[mix["kind"]]
    try:
        res = driver(session, params, mix, seed, seconds, num_targets, window)
        setup_s = window.t_begin - t_start
        peak = memory_peak(devs)
        planes = trace_mod.load(trace_dir) if trace else None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    log(f"[run] {workload}: setup {setup_s:.3f} s, "
        + ", ".join(f"{k} {v}" for k, v in res.items()
                    if isinstance(v, (int, float)) and not isinstance(v, bool)))

    # free the program before the reference runs
    del session, task
    gc.unfreeze()
    gc.collect()
    jax.clear_caches()

    ref = load_module(bench / "reference" / f"{cfg['model']}.py")
    t_ref = time.perf_counter()
    ref_sgs = ref.semantic_graphs(g, cfg)
    t_fwd = time.perf_counter()
    lo, hi, left_out = ref.forward(g, ref_sgs, params, cfg)
    gap, answers = compare(res, lo, hi, left_out)
    log(f"[reference] {time.perf_counter() - t_ref:.3f} s (forward "
        f"{time.perf_counter() - t_fwd:.3f} s); {answers} answers compared; "
        f"{int(left_out.sum())} of {left_out.size} rows left out, "
        f"{int((hi > lo).any(axis=1).sum())} bounded by an envelope")
    if not math.isfinite(gap):
        gap = sys.float_info.max  # no comparable answer: fails any limit
    limit = float(cfg["limits"]["logit_gap"])
    checks = {
        "logit_gap": {"value": gap, "limit": limit},
        "unanswered": {"value": int(res["failed"]), "limit": 0},
    }
    correct = bool(gap <= limit and res["failed"] == 0 and answers > 0)

    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": int(res["attempted"]),
           "failed": int(res["failed"])}
    if not trace:
        values = dict(res, setup_s=setup_s)
        metrics = {m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
                   for m in e2e}
    else:
        tr = trace_mod.Trace(planes)
        peaks = read_json(bench / "peaks.json")["devices"]
        if dev.device_kind not in peaks and not rehearse:
            raise RuntimeError(f"no peaks for device kind {dev.device_kind!r} in peaks.json")
        ctx = SimpleNamespace(
            trace=tr, run=res, cfg=cfg, cell=cell, peaks=peaks.get(dev.device_kind),
            cost=costs.forward_costs(adapter, g, ref_sgs, cfg), costs=costs,
        )
        metrics = {}
        for m in per_layer:
            v = load_module(bench / "metrics" / f"{m['name']}.py").read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
        breakdown = {"device_ops": tr.top_ops(10), "idle_gaps": tr.idle_by_span(10)}
    if rehearse:
        # a rehearsal's numbers are not device numbers: kept apart, unnamed
        out["rehearsal"] = {"metrics": metrics, "run": {
            k: v for k, v in res.items() if isinstance(v, (int, float))}}
    else:
        out["metrics"] = metrics
        out["device"] = device
        if trace:
            out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on any backend at --scale; no device metrics")
    ap.add_argument("--scale", type=float, default=1.0,
                    help="graph scale of a rehearsal (cells run at 1.0)")
    ap.add_argument("--precision", default=None,
                    help="matmul precision override: the control runs")
    a = ap.parse_args(argv)
    if a.scale != 1.0 and not a.rehearse:
        ap.error("--scale is for --rehearse only")
    try:
        out = run_cell(ROOT, a.workload, a.seed, a.seconds, bool(a.trace),
                       rehearse=a.rehearse, scale=a.scale, precision=a.precision,
                       log=lambda s: print(s, flush=True))
    except NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
