"""The program's own spans and stage scopes in a reduced trace.

The program writes host spans into the profiler's trace through
``repro.tracing``: ``serve.*`` in ``ServeFrontend`` and ``session.*`` in
``InferenceSession``, with their stats. Its device stages carry
compile-time scopes (``fp``, ``na.<semantic graph>``, ``k1`` and ``k2``
inside it, ``fusion``, ``gather``) in each HLO instruction's ``op_name``.
A trace names a device op by its HLO instruction text alone, so the scope
of an op comes from ``repro.tracing.op_scopes()``, the ``op_name`` of each
instruction of the programs compiled in this process, looked up under the
module the op ran in (the ``XLA Modules`` event around it).

Against a program without these (no such span, no ``repro.tracing``),
every function here finds nothing and returns an empty result.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Tuple

from bench.trace import OPS_LINE, Trace, is_device_plane

PREFIXES = ("bench.", "serve.", "session.")
WINDOW_SPAN = "bench.window"
MODULES_LINE = "XLA Modules"
KERNELS = ("k1", "k2")
STAGES = ("fp", "fusion", "gather")


def host_spans(tr: Trace, name: str) -> List[Tuple[int, int, dict]]:
    """``(start_ns, dur_ns, stats)`` of every host span named ``name`` that
    starts inside the window, in order of start."""
    out = [(ev[1], ev[2], ev[3]) for ev in _host_events(tr)
           if ev[0] == name and tr.t0 <= ev[1] < tr.t1]
    return sorted(out, key=lambda s: s[0])


def _host_events(tr: Trace):
    for p in tr.planes:
        if not is_device_plane(p["name"]):
            for ln in p["lines"]:
                yield from ln["events"]


def stat_sum(spans, *stats: str) -> float:
    """Σ over ``spans`` of the product of the named stats."""
    tot = 0.0
    for _, _, st in spans:
        v = 1.0
        for k in stats:
            v *= st[k]
        tot += v
    return tot


def per_request_ms(tr: Trace, name: str, *stats: str) -> Optional[float]:
    """Σ of the product of ``stats`` (microseconds) over the in-window
    spans ``name``, over Σ of their ``requests``, in milliseconds."""
    spans = [s for s in host_spans(tr, name) if "requests" in s[2]]
    n = stat_sum(spans, "requests")
    if not n:
        return None
    return stat_sum(spans, *stats) / n * 1e-3


def stage(op_name: Optional[str]) -> str:
    """The stage of an op from its scope path: ``na.<graph>/k1``,
    ``na.<graph>/k2``, ``na.<graph>/glue``, ``fp``, ``fusion``, ``gather``
    or ``unscoped``. The path's last part names the primitive (``gather``
    too), not a scope."""
    parts = op_name.split("/")[:-1] if op_name else []
    na = next((p for p in parts if p.startswith("na.")), None)
    if na is not None:
        kernel = next((k for k in KERNELS if k in parts), "glue")
        return f"{na}/{kernel}"
    return next((s for s in STAGES if s in parts), "unscoped")


def _program_scopes() -> Dict[str, Dict[str, str]]:
    try:
        from repro import tracing
    except ImportError:
        return {}
    return tracing.op_scopes()


def _lines(plane, name):
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln["events"]
    return []


def seconds_by_stage(tr: Trace) -> Dict[str, float]:
    """In-window device seconds of each stage (``stage``), averaged over the
    chips; empty where no program scope is known."""
    scopes = _program_scopes()
    if not scopes or not tr.devices:
        return {}
    merged: Dict[str, str] = {}
    for table in scopes.values():
        merged.update(table)
    tot: Dict[str, int] = {}
    for plane in tr.devices:
        mods = sorted((ev[1], ev[1] + ev[2], ev[0].split("(")[0])
                      for ev in _lines(plane, MODULES_LINE))
        starts = [m[0] for m in mods]
        for name, start, dur, _ in _lines(plane, OPS_LINE):
            s, e = max(start, tr.t0), min(start + dur, tr.t1)
            if e <= s:
                continue
            i = bisect.bisect_right(starts, start) - 1
            table = scopes.get(mods[i][2]) if i >= 0 and start < mods[i][1] else None
            instr = name.split(" = ", 1)[0].lstrip("%")
            key = stage((table or merged).get(instr))
            tot[key] = tot.get(key, 0) + e - s
    return {k: ns * 1e-9 / len(tr.devices) for k, ns in tot.items()}


def idle_by_span(tr: Trace, n: int = 10) -> List[List]:
    """Idle seconds per innermost span open at each gap's midpoint, as
    ``Trace.idle_by_span`` labels them, counting the program's ``serve.*``
    and ``session.*`` spans besides ``bench.*``."""
    spans = sorted((ev[1], ev[2], ev[0]) for ev in _host_events(tr)
                   if ev[0].startswith(PREFIXES) and ev[0] != WINDOW_SPAN)
    starts = [sp[0] for sp in spans]
    longest = max((sp[1] for sp in spans), default=0)
    tot: Dict[str, int] = {}
    for s, e in tr.idle_gaps():
        t = (s + e) // 2
        best = None
        # spans open at t started in [t - longest, t]
        i = bisect.bisect_right(starts, t)
        lo = bisect.bisect_left(starts, t - longest)
        for start, dur, name in spans[lo:i]:
            if t < start + dur and (best is None or dur < best[1]):
                best = (name, dur)
        lab = best[0] if best else "no bench span"
        tot[lab] = tot.get(lab, 0) + e - s
    k = max(len(tr.devices), 1)
    return [[lab, ns * 1e-9 / k]
            for lab, ns in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]
