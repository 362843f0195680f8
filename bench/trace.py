"""Reduction of a JAX profiler trace to the benchmark's device numbers.

``load(dir)`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into a
plain structure: ``[{"name": plane, "lines": [{"name": line, "events":
[[name, start_ns, dur_ns, {stat: value}], ...]}]}]``. :class:`Trace` works
on that structure only, so a test can feed it a small recorded excerpt.

* The window is the host span named ``bench.window``, which the harness
  opens around its measured window; every device interval is clipped to it.
* Device ops are the events of each TPU plane's ``XLA Ops`` line. Busy
  time is the union of their intervals, averaged over the chips.
* A kernel's time is the sum of the durations of the device ops whose name
  or whose string stats match a pattern.
* An idle gap is a stretch of the window in which a chip runs no op; it is
  labelled with the innermost ``bench.*`` host span open at its midpoint.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
NAME_CHARS = 160  # a device op is named by its HLO text; the head says which


def load(trace_dir: str) -> list:
    """The newest ``.xplane.pb`` under ``trace_dir`` as plain planes."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    planes = []
    for p in pd.planes:
        lines = []
        for ln in p.lines:
            evs = []
            for e in ln.events:
                stats = {}
                for k, v in e.stats:
                    if isinstance(v, (str, int, float)):
                        stats[str(k)] = v
                evs.append([e.name, int(e.start_ns), int(e.duration_ns), stats])
            lines.append({"name": ln.name, "events": evs})
        planes.append({"name": p.name, "lines": lines})
    return planes


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:") and "SparseCore" not in name


def _merge(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


class Trace:
    def __init__(self, planes: list):
        self.planes = planes
        spans = [ev for ev in self._host_events() if ev[0] == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"no {WINDOW_SPAN!r} host span in the trace")
        s = max(spans, key=lambda ev: ev[2])
        self.t0, self.t1 = s[1], s[1] + s[2]
        self.devices = [p for p in planes if is_device_plane(p["name"])]

    def _host_events(self):
        for p in self.planes:
            if is_device_plane(p["name"]):
                continue
            for ln in p["lines"]:
                yield from ln["events"]

    def _ops(self, plane) -> list:
        for ln in plane["lines"]:
            if ln["name"] == OPS_LINE:
                return ln["events"]
        return []

    def _clip(self, start: int, dur: int) -> Optional[Tuple[int, int]]:
        s, e = max(start, self.t0), min(start + dur, self.t1)
        return (s, e) if e > s else None

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def busy_intervals(self, plane) -> List[Tuple[int, int]]:
        iv = [self._clip(ev[1], ev[2]) for ev in self._ops(plane)]
        return _merge([x for x in iv if x is not None])

    @property
    def busy_s(self) -> float:
        """Seconds of the window in which a chip ran an op, averaged over
        the chips (0 where the trace has no device plane)."""
        if not self.devices:
            return 0.0
        tot = sum(e - s for p in self.devices for s, e in self.busy_intervals(p))
        return tot * 1e-9 / len(self.devices)

    def op_seconds(self, pattern: str) -> float:
        """Summed in-window device time of the ops matching ``pattern`` (a
        regex searched in the op's name and string stats), averaged over
        the chips."""
        rx = re.compile(pattern)
        tot = 0
        for p in self.devices:
            for name, start, dur, stats in self._ops(p):
                if rx.search(name) or any(
                    isinstance(v, str) and rx.search(v) for v in stats.values()
                ):
                    c = self._clip(start, dur)
                    if c:
                        tot += c[1] - c[0]
        return tot * 1e-9 / max(len(self.devices), 1)

    def top_ops(self, n: int = 10) -> List[List]:
        """The ``n`` op names with most in-window device time (seconds,
        averaged over the chips)."""
        tot: Dict[str, int] = {}
        for p in self.devices:
            for name, start, dur, _ in self._ops(p):
                c = self._clip(start, dur)
                if c:
                    tot[name] = tot.get(name, 0) + c[1] - c[0]
        k = max(len(self.devices), 1)
        return [[name[:NAME_CHARS], ns * 1e-9 / k]
                for name, ns in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self) -> List[Tuple[int, int]]:
        gaps = []
        for p in self.devices:
            cur = self.t0
            for s, e in self.busy_intervals(p):
                if s > cur:
                    gaps.append((cur, s))
                cur = max(cur, e)
            if self.t1 > cur:
                gaps.append((cur, self.t1))
        return gaps

    def _label(self, t: int, spans) -> str:
        best = None
        for name, start, dur in spans:
            if start <= t < start + dur and (best is None or dur < best[1]):
                best = (name, dur)
        return best[0] if best else "no bench span"

    def idle_by_span(self, n: int = 10) -> List[List]:
        """Idle seconds per label of what the host was doing (the innermost
        ``bench.*`` span at each gap's midpoint), largest first, averaged
        over the chips."""
        spans = [(ev[0], ev[1], ev[2]) for ev in self._host_events()
                 if ev[0].startswith(SPAN_PREFIX) and ev[0] != WINDOW_SPAN]
        tot: Dict[str, int] = {}
        for s, e in self.idle_gaps():
            lab = self._label((s + e) // 2, spans)
            tot[lab] = tot.get(lab, 0) + e - s
        k = max(len(self.devices), 1)
        return [[lab, ns * 1e-9 / k]
                for lab, ns in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]
