"""The traffic generator's arrival processes and id distributions, as the
mix files name them."""
import numpy as np
import pytest

from bench import load


@pytest.mark.parametrize("arrivals", [
    {"process": "poisson"},
    {"process": "onoff", "on_s": 1.0, "off_s": 1.5},
], ids=lambda a: a["process"])
def test_due_times_fill_the_window_with_the_same_work(arrivals):
    rate, seconds, n = 40.0, 7.0, 280
    runs = [load.due_times(arrivals, rate, seconds, n, 7, np.random.default_rng(s))
            for s in (1, 2)]
    for due in runs:
        assert due.size == n and due[0] == 0.0
        assert np.all(np.diff(due) >= 0) and due[-1] < seconds
    # another seed orders the same gaps anew
    assert not np.array_equal(runs[0], runs[1])
    if arrivals["process"] == "poisson":
        # the gaps, the last one up to the window's end, are the same set
        gaps = [np.sort(np.diff(np.append(d, seconds))) for d in runs]
        assert np.allclose(gaps[0], gaps[1])


def test_onoff_arrivals_fall_in_the_on_phases_only():
    due = load.due_times({"process": "onoff", "on_s": 1.0, "off_s": 1.5}, 40.0, 7.0,
                         280, 7, np.random.default_rng(3))
    phase = np.mod(due, 2.5)
    assert np.all(phase < 1.0)
    # three on-phases of 1 s (at 0, 2.5 and 5 s) share the window's requests
    counts = np.bincount((due // 2.5).astype(int), minlength=3)
    assert counts.sum() == 280 and np.all(counts > 60)


def test_uniform_ids_cover_the_targets():
    src = load.Ids({"dist": "uniform"}, np.random.default_rng(1), 50, 1)
    ids = np.concatenate([src.draw(4, 0.0) for _ in range(500)])
    assert ids.dtype == np.int32 and ids.min() >= 0 and ids.max() < 50
    assert np.unique(ids).size == 50


def test_zipf_ids_are_skewed_and_the_hot_set_rotates():
    spec = {"dist": "zipf", "exponent": 1.0, "rotate_s": 5.0}
    src = load.Ids(spec, np.random.default_rng(1), 1000, 9)
    first = np.concatenate([src.draw(4, t) for t in np.linspace(0, 4.9, 400)])
    second = np.concatenate([src.draw(4, t) for t in np.linspace(5, 9.9, 400)])
    hot = lambda ids: np.bincount(ids, minlength=1000).argmax()
    # the hottest id takes about 1/H(1000) = 13% of a phase's draws
    assert 0.08 < np.mean(first == hot(first)) < 0.2
    assert hot(first) != hot(second)
    # the hot set comes from the seed: a second source draws the same
    again = load.Ids(spec, np.random.default_rng(1), 1000, 9)
    assert np.array_equal(again.draw(4, 0.0), first[:4])


def test_unknown_process_or_distribution_is_refused():
    with pytest.raises(ValueError):
        load.due_times({"process": "gamma"}, 1.0, 1.0, 1, 0, np.random.default_rng(0))
    with pytest.raises(ValueError):
        load.Ids({"dist": "pareto"}, np.random.default_rng(0), 5, 0)
