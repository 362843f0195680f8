import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

# ---------------------------------------------------------------------------
# Multi-pod dry-run: for every (architecture × input shape × mesh) cell,
# lower + compile the step function against ShapeDtypeStruct stand-ins,
# print memory_analysis() (proves it fits) and cost_analysis() (FLOPs/bytes
# for §Roofline), parse collective bytes from the partitioned HLO, and write
# a JSON record benchmarks/roofline.py consumes.
#
# The two env lines above MUST run before any jax import: jax locks the
# device count at first init. setdefault lets tests inject smaller worlds.
# ---------------------------------------------------------------------------
import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402

from repro.configs import ALIASES, ARCHS, get_config  # noqa: E402
from repro.distributed import sharding as shlib  # noqa: E402
from repro.launch import steps as steps_lib  # noqa: E402
from repro.launch.mesh import make_mesh, make_production_mesh  # noqa: E402

# TPU v5e model constants (per chip)
PEAK_FLOPS = 197e12  # bf16
HBM_BW = 819e9  # B/s
ICI_BW = 50e9  # B/s per link

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute")
_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")


def collective_bytes(hlo_text: str):
    """Sum per-device result bytes of every collective op in partitioned HLO.

    Modeled link traffic: ring all-reduce moves ~2× the buffer; the others
    ~1×. The CPU backend promotes bf16 all-reduces to f32 (`.clone_promoted`
    computations) — a TPU keeps them bf16, so promoted ARs count at half
    width.
    """
    out = {k: 0 for k in _COLLS}
    counts = {k: 0 for k in _COLLS}
    for line in hlo_text.splitlines():
        for coll in _COLLS:
            token = f" {coll}("
            if token not in line and f" {coll}-start(" not in line:
                continue
            lhs = line.split("=", 1)
            if len(lhs) != 2:
                continue
            result = lhs[1].split(coll)[0]
            nbytes = 0
            for dt, dims in _SHAPE_RE.findall(result):
                if dt not in _DTYPE_BYTES:
                    continue
                n = 1
                for d in dims.split(","):
                    if d:
                        n *= int(d)
                nbytes += n * _DTYPE_BYTES[dt]
            if coll == "all-reduce" and "promoted" in line:
                nbytes //= 2  # CPU f32-promotion artifact; TPU stays bf16
            out[coll] += nbytes
            counts[coll] += 1
            break
    total = sum(v * (2 if k == "all-reduce" else 1) for k, v in out.items())
    return total, out, counts


def model_flops(cfg, shape: steps_lib.ShapeSpec) -> float:
    n = cfg.param_count()
    n_active = active_param_count(cfg)
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq
    return 2.0 * n_active * shape.global_batch  # decode: one token per row


def active_param_count(cfg) -> int:
    """Params touched per token (MoE: top-k experts instead of all)."""
    total = cfg.param_count()
    if cfg.moe is None:
        return total
    m = cfg.moe
    per_expert = cfg._mlp_params(m.expert_d_ff, cfg.d_model)
    n_moe_layers = sum(1 for k in cfg.pattern() if k == "M")
    inactive = n_moe_layers * (m.num_experts - m.top_k) * per_expert
    return total - inactive


def _probe_cfg(cfg, k: int, seq: int = 4096, accum: int = 1):
    """Shallow unrolled copy of cfg for exact cost accounting: k cycle units
    deep, scans fully unrolled (probe_mode), no grad accumulation."""
    c = len(cfg.cycle)
    n = k * c
    enc = 0
    if cfg.enc_layers:
        enc = max(1, round(cfg.enc_layers * n / cfg.num_layers))
    return dataclasses.replace(
        cfg, num_layers=n, enc_layers=enc, scan_layers=False, grad_accum=accum,
        # bigger flash chunks: same FLOPs/collectives, ~16x fewer unrolled
        # HLO ops (probe compile time); bytes shift <10% (fewer KV re-reads)
        attn_chunk_q=4096, attn_chunk_kv=4096,
        # cap unrolled RWKV chunk-scan length at 64 steps; overcounts the
        # intra-chunk attention term by <=13% at 32k (noted in EXPERIMENTS)
        rwkv_chunk=max(cfg.rwkv_chunk, seq // 64),
    )


def _probe_costs(cfg, shape, mesh, kind: str, rules=None):
    """Compile the probe and return (flops, bytes, coll_bytes) per device."""
    from repro.distributed.probe import probe_mode

    with jax.set_mesh(mesh), shlib.axis_rules(rules or {}), probe_mode():
        if kind == "train":
            params_s, opt_s = steps_lib.state_specs(cfg, with_opt=True)
            p_sh, o_sh = steps_lib.params_shardings(cfg, mesh, params_s, opt_s)
            fn = steps_lib.make_train_step(cfg, grad_shardings=p_sh)
            d_sh = steps_lib.data_shardings(cfg, shape, mesh)
            batch = steps_lib.input_specs(cfg, shape)
            jfn = jax.jit(fn, in_shardings=(p_sh, o_sh, d_sh),
                          out_shardings=(p_sh, o_sh, None))
            lowered = jfn.lower(params_s, opt_s, batch)
        elif kind == "prefill":
            fn = steps_lib.make_prefill_step(cfg, shape)
            params_s, _ = steps_lib.state_specs(cfg, with_opt=False)
            p_sh, _ = steps_lib.params_shardings(cfg, mesh, params_s)
            d_sh = steps_lib.data_shardings(cfg, shape, mesh)
            batch = steps_lib.input_specs(cfg, shape)
            cache_s = jax.eval_shape(lambda p, b: fn(p, b)[1], params_s, batch)
            c_sh = steps_lib.cache_shardings(cfg, shape, mesh, cache_s)
            jfn = jax.jit(fn, in_shardings=(p_sh, d_sh), out_shardings=(None, c_sh))
            lowered = jfn.lower(params_s, batch)
        else:
            fn = steps_lib.make_decode_step(cfg)
            params_s, _ = steps_lib.state_specs(cfg, with_opt=False)
            p_sh, _ = steps_lib.params_shardings(cfg, mesh, params_s)
            d = steps_lib.input_specs(cfg, shape)
            d_sh = steps_lib.data_shardings(cfg, shape, mesh)
            cache_s = steps_lib.cache_specs(cfg, shape)
            c_sh = steps_lib.cache_shardings(cfg, shape, mesh, cache_s)
            jfn = jax.jit(fn, in_shardings=(p_sh, d_sh["token"], d_sh["pos"], c_sh),
                          out_shardings=(None, c_sh))
            lowered = jfn.lower(params_s, d["token"], d["pos"], cache_s)
        compiled = lowered.compile()
    cost = _cost_dict(compiled)
    coll, _, _ = collective_bytes(compiled.as_text())
    return (
        float(cost.get("flops", 0.0)),
        float(cost.get("bytes accessed", 0.0)),
        float(coll),
    )


def _mem_dict(compiled):
    try:
        ma = compiled.memory_analysis()
        return {
            "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
            "output_bytes": getattr(ma, "output_size_in_bytes", None),
            "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(ma, "generated_code_size_in_bytes", None),
            "alias_bytes": getattr(ma, "alias_size_in_bytes", None),
            "repr": str(ma),
        }
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}


def _cost_dict(compiled):
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        return dict(ca)
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}


def _parse_val(v: str):
    if v in ("true", "True"):
        return True
    if v in ("false", "False"):
        return False
    if v in ("none", "None"):
        return None
    try:
        return int(v)
    except ValueError:
        pass
    try:
        return float(v)
    except ValueError:
        return v


def run_cell(
    arch: str,
    shape_name: str,
    mesh_kind: str,
    out_dir: Path,
    smoke: bool = False,
    mesh_override=None,
    ade_on: bool = True,
    verbose: bool = True,
    with_probe: bool = True,
    cfg_overrides: dict | None = None,
    tag_suffix: str = "",
    rules_override: dict | None = None,
):
    cfg = get_config(arch, smoke=smoke)
    if not ade_on and cfg.attn_prune_k is not None:
        cfg = dataclasses.replace(cfg, attn_prune_k=None)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = steps_lib.SHAPES[shape_name]
    if smoke:
        shape = steps_lib.smoke_shape(shape)
    ok, why = steps_lib.cell_supported(cfg, shape)
    rec = {
        "arch": cfg.name, "shape": shape_name, "mesh": mesh_kind,
        "kind": shape.kind, "seq": shape.seq, "global_batch": shape.global_batch,
        "params": cfg.param_count(), "active_params": active_param_count(cfg),
        "overrides": {k: str(v) for k, v in (cfg_overrides or {}).items()},
    }
    tag = f"{arch}_{shape_name}_{mesh_kind}{tag_suffix}"
    if not ok:
        rec.update(status="skipped", reason=why)
        _write(out_dir, tag, rec, verbose)
        return rec

    if mesh_override is not None:
        mesh = make_mesh(*mesh_override)
    else:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.devices.size
    rec["chips"] = int(n_chips)

    rules = {}
    if shape.name == "long_500k":
        # batch=1: nothing for the data axes to do on activations — spread
        # the KV/cache sequence over every axis instead.
        rules = {"cache_seq": ("pod", "data", "model")}
    if rules_override:
        rules.update(rules_override)

    t0 = time.time()
    try:
        with jax.set_mesh(mesh), shlib.axis_rules(rules):
            if shape.kind == "train":
                params_s, opt_s = steps_lib.state_specs(cfg, with_opt=True)
                p_sh, o_sh = steps_lib.params_shardings(cfg, mesh, params_s, opt_s)
                fn = steps_lib.make_train_step(cfg, grad_shardings=p_sh)
                d_sh = steps_lib.data_shardings(cfg, shape, mesh)
                batch = steps_lib.input_specs(cfg, shape)
                jfn = jax.jit(
                    fn,
                    in_shardings=(p_sh, o_sh, d_sh),
                    out_shardings=(p_sh, o_sh, None),
                    donate_argnums=(0, 1),
                )
                lowered = jfn.lower(params_s, opt_s, batch)
            elif shape.kind == "prefill":
                fn = steps_lib.make_prefill_step(cfg, shape)
                params_s, _ = steps_lib.state_specs(cfg, with_opt=False)
                p_sh, _ = steps_lib.params_shardings(cfg, mesh, params_s)
                d_sh = steps_lib.data_shardings(cfg, shape, mesh)
                batch = steps_lib.input_specs(cfg, shape)
                cache_s = jax.eval_shape(
                    lambda p, b: fn(p, b)[1], params_s, batch
                )
                c_sh = steps_lib.cache_shardings(cfg, shape, mesh, cache_s)
                jfn = jax.jit(fn, in_shardings=(p_sh, d_sh), out_shardings=(None, c_sh))
                lowered = jfn.lower(params_s, batch)
            else:  # decode
                fn = steps_lib.make_decode_step(cfg)
                params_s, _ = steps_lib.state_specs(cfg, with_opt=False)
                p_sh, _ = steps_lib.params_shardings(cfg, mesh, params_s)
                d = steps_lib.input_specs(cfg, shape)
                d_sh = steps_lib.data_shardings(cfg, shape, mesh)
                cache_s = steps_lib.cache_specs(cfg, shape)
                c_sh = steps_lib.cache_shardings(cfg, shape, mesh, cache_s)
                jfn = jax.jit(
                    fn,
                    in_shardings=(p_sh, d_sh["token"], d_sh["pos"], c_sh),
                    out_shardings=(None, c_sh),
                    donate_argnums=(3,),
                )
                lowered = jfn.lower(params_s, d["token"], d["pos"], cache_s)
            t_lower = time.time() - t0
            t0c = time.time()
            compiled = lowered.compile()
            t_compile = time.time() - t0c
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-4000:])
        _write(out_dir, tag, rec, verbose)
        return rec

    mem = _mem_dict(compiled)
    cost = _cost_dict(compiled)
    hlo = compiled.as_text()
    coll_total, coll_by_kind, coll_counts = collective_bytes(hlo)

    # XLA's cost analysis visits `while` bodies once, so the scanned module
    # under-counts. Probe: compile unrolled shallow copies at 1 and 2 cycle
    # units and extrapolate linearly to the real depth (see DESIGN.md §6).
    probe = {}
    t0p = time.time()
    try:
        if not with_probe:
            raise RuntimeError("probe disabled (multi-pod pass is proof-only)")
        p1 = _probe_costs(_probe_cfg(cfg, 1, shape.seq), shape, mesh, shape.kind, rules)
        p2 = _probe_costs(_probe_cfg(cfg, 2, shape.seq), shape, mesh, shape.kind, rules)
        units = cfg.num_layers / len(cfg.cycle)
        m = cfg.grad_accum if shape.kind == "train" else 1
        if m > 1 and shape.global_batch % 2 == 0:
            # per-microbatch costs (FSDP weight re-gathers/re-reads) scale
            # with accum: fit cost = A + d·B + d·a·C from a third probe at
            # (d=1, a=2), then evaluate at (units, grad_accum).
            p3 = _probe_costs(
                _probe_cfg(cfg, 1, shape.seq, accum=2), shape, mesh, shape.kind, rules
            )
            def fit(i):
                # clamp: per-layer and per-microbatch terms are physically
                # non-negative; compile-to-compile noise can invert tiny ones
                C = max(0.0, p3[i] - p1[i])
                B = max(0.0, p2[i] - p1[i] - C)
                A = max(0.0, p1[i] - B - C)
                return A + units * B + units * m * C
            flops, bytes_acc, coll_total = fit(0), fit(1), fit(2)
            probe_extra = {"probe_d1_a2": {"flops": p3[0], "bytes": p3[1], "coll": p3[2]}}
        else:
            flops = p1[0] + (p2[0] - p1[0]) * (units - 1)
            bytes_acc = p1[1] + (p2[1] - p1[1]) * (units - 1)
            coll_total = p1[2] + (p2[2] - p1[2]) * (units - 1)
            probe_extra = {}
        probe = {
            "probe_d1": {"flops": p1[0], "bytes": p1[1], "coll": p1[2]},
            "probe_d2": {"flops": p2[0], "bytes": p2[1], "coll": p2[2]},
            **probe_extra,
            "units": units,
            "accum": m,
            "probe_s": round(time.time() - t0p, 2),
        }
    except Exception as e:
        probe = {"probe_error": f"{type(e).__name__}: {e}"}
        flops = float(cost.get("flops", 0.0))
        bytes_acc = float(cost.get("bytes accessed", 0.0))
    # cost_analysis of the partitioned module reports per-device numbers.
    t_comp = flops / PEAK_FLOPS
    t_mem = bytes_acc / HBM_BW
    t_coll = coll_total / ICI_BW
    dom = max(("compute", t_comp), ("memory", t_mem), ("collective", t_coll),
              key=lambda kv: kv[1])[0]
    mflops = model_flops(cfg, shape)
    rec.update(
        status="ok",
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        probe=probe,
        scanned_cost=cost,
        flops_per_device=flops,
        bytes_per_device=bytes_acc,
        collective_bytes_per_device=coll_total,
        collectives=coll_by_kind,
        collective_counts=coll_counts,
        memory=mem,
        t_compute=t_comp,
        t_memory=t_mem,
        t_collective=t_coll,
        dominant=dom,
        model_flops_total=mflops,
        model_flops_per_device=mflops / n_chips,
        useful_flops_ratio=(mflops / n_chips) / flops if flops else None,
        roofline_fraction=(mflops / n_chips / PEAK_FLOPS)
        / max(t_comp, t_mem, t_coll)
        if max(t_comp, t_mem, t_coll) > 0
        else None,
        hlo_bytes=len(hlo),
    )
    _write(out_dir, tag, rec, verbose)
    return rec


def _write(out_dir: Path, tag: str, rec, verbose: bool):
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / f"{tag}.json"
    path.write_text(json.dumps(rec, indent=1, default=str))
    if verbose:
        if rec["status"] == "ok":
            print(
                f"[dryrun] {tag}: OK lower={rec['lower_s']}s compile={rec['compile_s']}s "
                f"flops/dev={rec['flops_per_device']:.3e} bytes/dev={rec['bytes_per_device']:.3e} "
                f"coll/dev={rec['collective_bytes_per_device']:.3e} dominant={rec['dominant']} "
                f"roofline_frac={rec['roofline_fraction'] and round(rec['roofline_fraction'],4)}",
                flush=True,
            )
            print(f"[dryrun] {tag} memory: {rec['memory'].get('repr')}", flush=True)
        else:
            print(f"[dryrun] {tag}: {rec['status']} {rec.get('reason', rec.get('error',''))}", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all", choices=["all"] + list(steps_lib.SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--smoke", action="store_true", help="reduced configs/shapes")
    ap.add_argument("--mesh-shape", default=None, help="e.g. 2,4 (test meshes)")
    ap.add_argument("--mesh-axes", default=None, help="e.g. data,model")
    ap.add_argument("--no-ade", action="store_true", help="disable attn pruning")
    ap.add_argument("--no-probe", action="store_true",
                    help="skip cost probes (compile proof only)")
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (hillclimb runs)")
    ap.add_argument("--rule", action="append", default=[],
                    help="sharding rule override name=ax1+ax2 (hillclimb)")
    ap.add_argument("--tag", default="", help="output filename suffix")
    args = ap.parse_args()
    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        overrides[k] = _parse_val(v)
    rules_ov = {}
    for kv in args.rule:
        k, v = kv.split("=", 1)
        rules_ov[k] = tuple(a for a in v.split("+") if a)

    archs = list(ARCHS) if args.arch == "all" else [ALIASES.get(args.arch, args.arch)]
    shapes = list(steps_lib.SHAPES) if args.shape == "all" else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    override = None
    if args.mesh_shape:
        override = (
            tuple(int(x) for x in args.mesh_shape.split(",")),
            tuple(args.mesh_axes.split(",")),
        )
    out_dir = Path(args.out)
    n_ok = n_bad = 0
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                rec = run_cell(
                    arch, shape, mesh_kind, out_dir,
                    smoke=args.smoke, mesh_override=override,
                    ade_on=not args.no_ade,
                    with_probe=not args.no_probe,
                    cfg_overrides=overrides or None,
                    tag_suffix=args.tag,
                    rules_override=rules_ov or None,
                )
                if rec["status"] == "error":
                    n_bad += 1
                else:
                    n_ok += 1
    print(f"[dryrun] done: {n_ok} ok/skipped, {n_bad} errors", flush=True)
    raise SystemExit(1 if n_bad else 0)


if __name__ == "__main__":
    main()
