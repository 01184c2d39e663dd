"""The traffic generator: one schedule per seed, the same work for every
seed, in its own order or in one fixed order."""

from __future__ import annotations

import json

import numpy as np
import pytest

import bench_tiny_tree as tiny

from harness import traffic

FILES = sorted((tiny.BENCH / "traffic").glob("*.json"))
SLOTS = 64


def _key(reqs):
    return [(r.due_s, r.max_new, r.prompt.tobytes()) for r in reqs]


@pytest.mark.parametrize("path", FILES, ids=lambda p: p.stem)
def test_same_seed_same_schedule_other_seed_other(path):
    t = json.loads(path.read_text())
    a = traffic.schedule(t, 2 ** 33 + 7, 30.0, 151936, SLOTS)
    b = traffic.schedule(t, 2 ** 33 + 7, 30.0, 151936, SLOTS)
    c = traffic.schedule(t, 2 ** 33 + 8, 30.0, 151936, SLOTS)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)


@pytest.mark.parametrize("path", FILES, ids=lambda p: p.stem)
def test_every_seed_gets_the_same_sizes_within_bounds(path):
    t = json.loads(path.read_text())
    runs = [traffic.schedule(t, s, 30.0, 65536, SLOTS) for s in (1, 2, 3)]
    sizes = [sorted((len(r.prompt), r.max_new) for r in rs) for rs in runs]
    assert sizes[0] != [(len(r.prompt), r.max_new) for r in runs[0]] \
        or len(runs[0]) == 1
    for rs in runs:
        assert sorted(len(r.prompt) for r in rs) \
            == sorted(len(r.prompt) for r in runs[0])
        assert sorted(r.max_new for r in rs) \
            == sorted(r.max_new for r in runs[0])
        # a backlog's first requests are already in service: their
        # replies are residual lives, 1 up to the longest reply
        head = SLOTS if t["kind"] == "backlog" else 0
        for i, r in enumerate(rs):
            p, o = t["prompt_tokens"], t["output_tokens"]
            assert p["min"] <= len(r.prompt) <= p["max"]
            assert (1 if i < head else o["min"]) <= r.max_new <= o["max"]
            assert r.prompt.dtype == np.int32 and r.prompt.max() < 65536


def test_open_loop_arrivals_cover_warm_in_and_window():
    t = dict(tiny.TRAFFIC["chat"], warm_s=5.0)
    t["arrivals"] = {"process": "poisson", "rate_per_s": 10.0}
    reqs = traffic.schedule(t, 3, 20.0, 512, SLOTS)
    due = np.array([r.due_s for r in reqs])
    assert len(reqs) == 250
    assert np.all(np.diff(due) > 0)
    assert -5.0 < due[0] < -4.0 and 19.0 < due[-1] < 20.0
    assert np.sum(due < 0) == 50
    # in a fixed order every seed sends the same sizes at the same times,
    # with its own ids
    other = traffic.schedule(t, 4, 20.0, 512, SLOTS)
    assert [(r.due_s, len(r.prompt), r.max_new) for r in reqs] \
        == [(r.due_s, len(r.prompt), r.max_new) for r in other]
    assert any(not np.array_equal(a.prompt, b.prompt)
               for a, b in zip(reqs, other))


def test_open_loop_in_the_seeds_order():
    """Ordered by the seed, every seed puts the same requests in the
    window, in its own order and at its own times."""
    t = dict(tiny.TRAFFIC["chat"], warm_s=5.0, order="seed")
    t["arrivals"] = {"process": "poisson", "rate_per_s": 10.0}
    reqs, other = (traffic.schedule(t, s, 20.0, 512, SLOTS) for s in (3, 4))
    window = [sorted((len(r.prompt), r.max_new) for r in rs if r.due_s >= 0)
              for rs in (reqs, other)]
    assert window[0] == window[1]
    assert [r.max_new for r in reqs] != [r.max_new for r in other]
    assert not np.allclose([r.due_s for r in reqs],
                           [r.due_s for r in other])


def test_unknown_order_is_refused():
    t = dict(tiny.TRAFFIC["chat"], order="shuffled")
    with pytest.raises(ValueError, match="order"):
        traffic.schedule(t, 3, 20.0, 512, SLOTS)


def test_lognormal_quantiles_have_the_stated_median():
    q = traffic.quantiles({"dist": "lognormal", "median": 256, "sigma": 0.8,
                           "min": 16, "max": 2048}, 1001)
    assert q[500] == 256 and q.min() >= 16 and q.max() <= 2048


def test_backlog_fills_the_slots_with_the_same_requests_for_every_seed():
    t = json.loads((tiny.BENCH / "traffic" / "longgen.json").read_text())
    a, b = (traffic.schedule(t, s, 51.0, 65536, SLOTS)
            for s in (5, 2 ** 40 + 5))
    block = t["backlog"]
    bounds = [0] + list(range(SLOTS, t["requests"], block))
    for lo, hi in zip(bounds, bounds[1:] + [t["requests"]]):
        pairs = [sorted((len(r.prompt), r.max_new) for r in rs[lo:hi])
                 for rs in (a, b)]
        assert pairs[0] == pairs[1]
    assert len(a) == t["requests"]
    assert [r.max_new for r in a[:SLOTS]] != [r.max_new for r in b[:SLOTS]]
    # the slots start with residual lives, the backlog behind them with
    # whole replies
    assert sorted(r.max_new for r in a[:SLOTS]) == list(
        traffic.residual_quantiles(t["output_tokens"], SLOTS))
    assert min(r.max_new for r in a[SLOTS:]) >= t["output_tokens"]["min"]


def test_residual_lives_of_a_uniform_reply():
    """Residual life of L ~ U[1024, 4096]: mean E[L^2] / (2 E[L]) = 1,434
    tokens, every value in 1..4096, short lives as common as any below
    1024 (P(L >= r) = 1 there)."""
    spec = {"dist": "uniform", "min": 1024, "max": 4096}
    r = traffic.residual_quantiles(spec, 4096)
    assert r.min() >= 1 and r.max() <= 4096
    assert abs(r.mean() - 1434) < 5
    below = r[r < 1024]
    assert abs(len(below) / len(r) - 1024 / 2560) < 0.01
    assert np.all(np.diff(r) >= 0)
