"""``correct`` comes out false when the timed path is broken underneath.

Each case drives a whole run of a cell (a CPU rehearsal at a small graph
scale; the harness's look for a chip is skipped) with the program patched
under the window:

* ``control`` — the plain reference put in the program's place, its
  matrix products at three-pass bfloat16 (JAX's ``"high"``), the nearest
  precision below the configurations' ``"highest"``;
* ``altered`` — one answer changed where it is produced (one logit moved
  by 0.1% of the largest);
* ``half`` — half of every output left out, the mean of the other half in
  its place.

A run with nothing patched comes out correct.
"""
import contextlib
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
SCALE = 0.02


def _run(workload, seed=5):
    from bench import run

    return run.run_cell(ROOT, workload, seed, 1.0, False, rehearse=True,
                        scale=SCALE, log=lambda s: None)


def _control_logits(workload, params):
    from bench import graph, run
    from bench.reference.common import dot_bf16x3

    _, _, cfg, _, _, _ = run.find_cell(ROOT, workload)
    ref = run.load_module(ROOT / "bench" / "reference" / f"{cfg['model']}.py")
    g = graph.make_graph(cfg["graph"], scale=SCALE)
    lo, hi, _ = ref.forward(g, ref.semantic_graphs(g, cfg), params, cfg, dot=dot_bf16x3)
    return jnp.asarray((lo + hi) / 2)


def _alter(out):
    return out.at[0, 0].add(1e-3 * jnp.abs(out).max())


def _half(out):
    h = out.shape[0] // 2
    if h == 0:
        return out
    return out.at[h:].set(out[:h].mean(axis=0))


@contextlib.contextmanager
def _broken(kind, workload, monkeypatch):
    from repro.core.session import InferenceSession

    call, query = InferenceSession.__call__, InferenceSession.query
    memo = {}

    def full(self, params):
        if kind == "control":
            if "x" not in memo:
                memo["x"] = _control_logits(workload, params)
            return memo["x"]
        out = call(self, params)
        return _alter(out) if kind == "altered" else _half(out)

    def block(self, params, idx):
        if kind == "control":
            return full(self, params)[jnp.asarray(idx)]
        out = query(self, params, idx)
        return _alter(out) if kind == "altered" else _half(out)

    monkeypatch.setattr(InferenceSession, "__call__", full)
    monkeypatch.setattr(InferenceSession, "query", block)
    yield


CASES = [
    ("han-dblp.full", "control"), ("han-dblp.full", "altered"), ("han-dblp.full", "half"),
    ("han-dblp.serve", "control"), ("han-dblp.serve", "altered"), ("han-dblp.serve", "half"),
    ("han-dblp.closed", "altered"),
    ("simplehgn-acm.full", "control"), ("simplehgn-acm.full", "altered"),
    ("simplehgn-acm.full", "half"),
]


@pytest.mark.parametrize("workload,kind", CASES, ids=lambda x: x)
def test_broken_path_is_not_correct(workload, kind, monkeypatch):
    with _broken(kind, workload, monkeypatch):
        out = _run(workload)
    assert out["correct"] is False, out["checks"]
    assert out["checks"]["logit_gap"]["value"] > out["checks"]["logit_gap"]["limit"]


@pytest.mark.parametrize("workload", ["han-dblp.full", "han-dblp.serve", "simplehgn-acm.full"])
def test_sound_run_is_correct(workload):
    out = _run(workload)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"


def test_no_chip_is_refused():
    from bench import run

    with pytest.raises(run.NoChip):
        run.run_cell(ROOT, "han-dblp.full", 1, 1.0, False, log=lambda s: None)


def test_near_tie_rows_are_flagged():
    from bench.reference.common import select_top_k

    rank = jnp.asarray([[3.0, 2.0, 1.0 + 1e-7, 1.0, 0.0],
                        [3.0, 2.0, 1.5, 1.0, 0.0]])
    mask = jnp.ones_like(rank, bool)
    src = jnp.asarray([[0, 1, 2, 3, 4], [0, 1, 2, 3, 4]])
    slots, kept, tie, alt = select_top_k(rank, mask, src, 3)
    assert np.asarray(kept).all()
    assert np.asarray(tie).tolist() == [True, False]
    # the near-tie row's alternative keeps the rival in place of the K-th
    assert np.asarray(slots).tolist() == [[0, 1, 2], [0, 1, 2]]
    assert np.asarray(alt).tolist() == [[0, 1, 3], [0, 1, 2]]
    # the same source on both sides of the K-th place is no rival
    src_dup = jnp.asarray([[0, 1, 2, 2, 4], [0, 1, 2, 3, 4]])
    _, _, tie, _ = select_top_k(rank, mask, src_dup, 3)
    assert np.asarray(tie).tolist() == [False, False]


@pytest.mark.parametrize("workload,rtol", [("han-dblp.full", 1e-2), ("simplehgn-acm.full", 1e-3)],
                         ids=lambda x: str(x))
def test_envelope_bounds_near_ties_resolved_either_way(workload, rtol, monkeypatch):
    """A stand-in program that keeps the rival at every other near-tie row
    (in every layer) lies inside the reference's envelope on every row the
    reference compares. A wide near-tie band makes ties common here."""
    import jax

    from bench import graph, run
    from bench.reference import common

    _, _, cfg, _, _, _ = run.find_cell(ROOT, workload)
    ref = run.load_module(ROOT / "bench" / "reference" / f"{cfg['model']}.py")
    adapter = run.load_module(ROOT / "bench" / "models" / f"{cfg['model']}.py")
    g = graph.make_graph(cfg["graph"], scale=SCALE)
    sgs = ref.semantic_graphs(g, cfg)
    params = run.init_params(5, adapter.param_shapes(cfg, g, list(sgs)))
    monkeypatch.setattr(common, "NEAR_TIE_RTOL", rtol)
    jax.clear_caches()
    lo, hi, left_out = ref.forward(g, sgs, params, cfg)
    assert (hi > lo).any(axis=1).sum() > 0 and not left_out.all()

    real = common.select_top_k

    def stand_in(flip):
        def select(rank, mask, src, k):
            slots, kept, tie, alt = real(rank, mask, src, k)
            pick = flip & tie & (jnp.arange(tie.shape[0]) % 2 == 0)
            sel = jnp.where(pick[:, None], alt, slots)
            return sel, kept, jnp.zeros_like(tie), sel

        monkeypatch.setattr(ref, "select_top_k", select)
        jax.clear_caches()
        out, out_hi, _ = ref.forward(g, sgs, params, cfg)
        jax.clear_caches()
        assert np.array_equal(out, out_hi)
        return out

    own, prog = stand_in(False), stand_in(True)
    keep = ~left_out
    tol = 1e-6 * np.abs(hi).max()
    for out in (own, prog):
        assert np.all(out[keep] >= lo[keep] - tol) and np.all(out[keep] <= hi[keep] + tol)
    # the flips moved compared rows well beyond rounding
    assert np.abs(prog - own)[keep].max() > 100 * tol
