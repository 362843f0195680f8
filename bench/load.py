"""The general traffic generator: one driver per ``kind`` of mix file.

* ``offline`` — whole-graph forwards (``session(params)``), each ended by
  ``block_until_ready``, back to back for the window.
* ``open`` — independent clients: ``rate_rps`` requests a second through
  ``ServeFrontend.submit``, sent on schedule whether or not earlier ones
  have finished. A request is timed from when it was due.
* ``closed`` — ``clients`` callers, each sending its next request only
  after its reply.

The mix names its arrival process and its id distribution as data:

* ``arrivals`` (open loop): ``{"process": "poisson"}``, or
  ``{"process": "onoff", "on_s": a, "off_s": b}``: bursts of ``a``
  seconds, Poisson at ``rate_rps * (a + b) / a``, each followed by ``b``
  seconds with no arrival, so the mean rate is ``rate_rps``.
* ``ids``: ``{"dist": "uniform"}`` over the label type's targets, or
  ``{"dist": "zipf", "exponent": s, "rotate_s": r}``: the target of rank
  ``i`` drawn with weight ``1 / (i + 1) ** s``, ranks mapped to targets by
  a permutation drawn anew every ``r`` seconds of the window (the hot set
  rotates).

The work is fixed by the mix and the window, and the seed only orders it:
an open-loop run sends ``round(rate * seconds)`` requests whose gaps (drawn
once from the mix's ``schedule_seed``, scaled to fill the window, or the
on-phases of an on/off process) and sizes (an equal share of each size in
``ids_per_request``) are permuted by the run's seed; the target ids are
drawn from the run's seed.

Drivers call ``window.begin()`` just before the measured window and
``window.end()`` just after it, and wrap host work in ``span(name)``.
"""
from __future__ import annotations

import collections
import queue
import threading
import time

import numpy as np

from bench.window import span

WARM_REQUESTS = 48


def _sizes(mix, n, rng):
    lo, hi = mix["ids_per_request"]
    return rng.permutation(lo + np.arange(n) % (hi - lo + 1))


def due_times(arrivals: dict, rate: float, seconds: float, n: int,
              schedule_seed: int, rng) -> np.ndarray:
    """The due times (seconds from the window's start) of ``n`` requests:
    gaps drawn once from ``schedule_seed`` over the time in which requests
    arrive, scaled to fill it and permuted by ``rng``."""
    process = arrivals["process"]
    if process == "poisson":
        on, off = seconds, 0.0
    elif process == "onoff":
        on, off = float(arrivals["on_s"]), float(arrivals["off_s"])
    else:
        raise ValueError(f"unknown arrival process {process!r}")
    period = on + off
    whole, part = divmod(seconds, period)
    on_total = whole * on + min(on, part)  # the window's arrival time
    gaps = np.random.default_rng(schedule_seed).exponential(1.0 / rate, n)
    gaps *= on_total / gaps.sum()
    gaps = rng.permutation(gaps)
    u = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    # arrival time -> wall time: the k-th on-phase starts at k * period
    k = np.floor(u / on)
    return k * period + (u - k * on)


class Ids:
    """Target ids of requests, drawn from ``rng`` by the mix's ``ids``."""

    def __init__(self, spec: dict, rng, num_targets: int, seed: int):
        self.dist, self.rng, self.n, self.seed = spec["dist"], rng, num_targets, seed
        if self.dist == "zipf":
            w = 1.0 / np.arange(1, num_targets + 1, dtype=np.float64) ** float(spec["exponent"])
            self.cdf = np.cumsum(w) / w.sum()
            self.rotate_s = float(spec["rotate_s"])
            self.perms: dict = {}
        elif self.dist != "uniform":
            raise ValueError(f"unknown id distribution {self.dist!r}")

    def draw(self, size: int, t: float) -> np.ndarray:
        """``size`` ids for a request sent ``t`` seconds into the window."""
        if self.dist == "uniform":
            return self.rng.integers(0, self.n, size).astype(np.int32)
        ranks = np.minimum(np.searchsorted(self.cdf, self.rng.random(size)), self.n - 1)
        epoch = max(0, int(t // self.rotate_s))
        if epoch not in self.perms:
            # each epoch's hot set comes from the seed and the epoch alone
            self.perms[epoch] = np.random.default_rng([self.seed, 2, epoch]).permutation(self.n)
        return self.perms[epoch][ranks].astype(np.int32)


def offline(session, params, mix, seed, seconds, num_targets, window):
    import jax

    for _ in range(2):  # warm: load the executable, touch every buffer
        jax.block_until_ready(session(params))
    outs = []
    window.begin()
    t0 = time.perf_counter()
    while True:
        with span("bench.forward"):
            out = session(params)
            out.block_until_ready()
        outs.append(out)
        if time.perf_counter() - t0 >= seconds:
            break
    elapsed = time.perf_counter() - t0
    window.end()
    return {
        "attempted": len(outs), "failed": 0, "forwards": len(outs),
        "elapsed_s": elapsed, "infer_ms": elapsed / len(outs) * 1e3,
        "outputs": outs,
    }


def _frontend(session, params, mix):
    from repro.serve import BatchPolicy, ServeFrontend, SystemClock, ThreadExecutor

    policy = BatchPolicy(capacities=tuple(mix["capacities"]),
                         flush_timeout=float(mix["flush_timeout_s"]))
    fe = ServeFrontend(session, params, policy, clock=SystemClock(),
                       executor=ThreadExecutor())
    return fe.start()


def _warm(fe, mix, num_targets, seed):
    """Every block capacity of the ladder, before the window: a burst that
    fills the largest blocks, then single requests of each size."""
    rng = np.random.default_rng([seed, 1])
    lo, hi = mix["ids_per_request"]
    futs = [fe.submit(rng.integers(0, num_targets, int(s)).astype(np.int32))
            for s in rng.integers(lo, hi + 1, WARM_REQUESTS)]
    for f in futs:
        f.result(timeout=300)
    for s in range(lo, hi + 1):
        fe.submit(rng.integers(0, num_targets, s).astype(np.int32)).result(timeout=300)


def _collect(futs, ids):
    rows, failed = [], 0
    for f, i in zip(futs, ids):
        if f is not None and f.done() and f.exception(timeout=0) is None:
            rows.append((i, np.asarray(f.result(timeout=0))))
        else:
            rows.append((i, None))
            failed += 1
    return rows, failed


def open_loop(session, params, mix, seed, seconds, num_targets, window):
    rate = float(mix["rate_rps"])
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng(seed)
    due = due_times(mix["arrivals"], rate, seconds, n, mix["schedule_seed"], rng)
    source = Ids(mix["ids"], rng, num_targets, seed)
    ids = [source.draw(int(s), t) for s, t in zip(_sizes(mix, n, rng), due)]
    fe = _frontend(session, params, mix)
    try:
        _warm(fe, mix, num_targets, seed)
        futs = [None] * n
        t_done = np.full(n, np.nan)
        late = np.zeros(n)
        handoff: "queue.Queue" = queue.Queue()
        deadline = [None]

        def reap():
            for _ in range(n):
                i, f = handoff.get()
                if f is not None:
                    with span("bench.await"):
                        f.wait(max(0.0, deadline[0] - time.perf_counter()))
                t_done[i] = time.perf_counter()

        reaper = threading.Thread(target=reap, name="bench-reaper", daemon=True)
        blocks0 = fe.stats.blocks
        window.begin()
        t0 = time.perf_counter()
        deadline[0] = t0 + seconds + float(mix["drain_s"])
        reaper.start()
        for i in range(n):
            dt = t0 + due[i] - time.perf_counter()
            if dt > 0:
                time.sleep(dt)
            late[i] = time.perf_counter() - (t0 + due[i])
            with span("bench.submit"):
                try:
                    futs[i] = fe.submit(ids[i])
                except Exception:  # noqa: BLE001 - a refused request failed
                    futs[i] = None
            handoff.put((i, futs[i]))
        rest = t0 + seconds - time.perf_counter()
        if rest > 0:
            time.sleep(rest)
        t_end = time.perf_counter()
        blocks = fe.stats.blocks - blocks0
        backlog = len(fe.queue)
        window.end()
        reaper.join(max(0.0, deadline[0] - time.perf_counter()) + 1.0)
    finally:
        fe.close()
    rows, failed = _collect(futs, ids)
    lat = t_done - (t0 + due)
    missing = np.array([r is None for _, r in rows])
    # an unanswered request misses every limit: it counts as the whole wait
    lat[missing] = deadline[0] - (t0 + due[missing])
    return {
        "attempted": n, "failed": failed, "rows": rows, "elapsed_s": t_end - t0,
        "latency_s": lat, "p95_ms": nearest_rank(lat, 95) * 1e3,
        "blocks": blocks, "backlog_at_end": backlog,
        "generator_late_ms": float(late.max()) * 1e3,
    }


def closed_loop(session, params, mix, seed, seconds, num_targets, window):
    clients = int(mix["clients"])
    rng = np.random.default_rng(seed)
    cycle = _sizes(mix, 4096, rng)
    source = Ids(mix["ids"], rng, num_targets, seed)
    fe = _frontend(session, params, mix)
    try:
        _warm(fe, mix, num_targets, seed)
        inflight = collections.deque()
        futs, ids, t_done = [], [], []
        stats0 = (fe.stats.valid_slots, fe.stats.padded_slots, fe.stats.blocks)

        def send():
            x = source.draw(int(cycle[len(ids) % cycle.size]), time.perf_counter() - t0)
            with span("bench.submit"):
                try:
                    f = fe.submit(x)
                except Exception:  # noqa: BLE001 - a refused request failed
                    f = None
            ids.append(x)
            futs.append(f)
            t_done.append(np.nan)
            inflight.append(len(futs) - 1)

        window.begin()
        t0 = time.perf_counter()
        t_end = t0 + seconds
        deadline = t_end + float(mix["drain_s"])
        for _ in range(clients):
            send()
        stats1 = None
        while inflight:
            i = inflight.popleft()
            if futs[i] is not None:
                with span("bench.await"):
                    futs[i].wait(max(0.0, deadline - time.perf_counter()))
            now = time.perf_counter()
            t_done[i] = now
            if now < t_end:
                send()
            elif stats1 is None:
                stats1 = (fe.stats.valid_slots, fe.stats.padded_slots, fe.stats.blocks)
                window.end()
        if stats1 is None:
            stats1 = (fe.stats.valid_slots, fe.stats.padded_slots, fe.stats.blocks)
            window.end()
    finally:
        fe.close()
    rows, failed = _collect(futs, ids)
    t_done = np.asarray(t_done)
    ok = np.array([r is not None for _, r in rows])
    served = int(np.sum(ok & (t_done <= t_end)))
    valid, padded = stats1[0] - stats0[0], stats1[1] - stats0[1]
    return {
        "attempted": len(futs), "failed": failed, "rows": rows,
        "elapsed_s": seconds, "served_rps": served / seconds,
        "blocks": stats1[2] - stats0[2],
        "valid_slots": valid, "padded_slots": padded,
    }


def nearest_rank(values, q):
    """The ``q``-th percentile by nearest rank (an infinite value, a
    request never answered, sorts last)."""
    v = np.sort(np.asarray(values, float))
    return float(v[max(0, int(np.ceil(q / 100.0 * v.size)) - 1)])


DRIVERS = {"offline": offline, "open": open_loop, "closed": closed_loop}
