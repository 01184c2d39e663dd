"""One general generator: a traffic file's parameters to a request schedule.

Kinds of traffic (``"kind"`` in the file):

* ``open_loop``: independent users. Arrivals follow ``arrivals`` (a
  Poisson process at ``rate_per_s``) from ``warm_s`` seconds before the
  window opens to its close; each request is timed from the moment it was
  due, whatever the server is doing. The warm-in and the window are drawn
  as two segments, each with rate × its length arrivals, so every seed
  puts the same requests in the window.
* ``backlog``: a queue of batch jobs that never empties. ``backlog``
  requests wait beyond the engine's slots, and each one that finishes is
  replaced at once; ``requests`` bounds the list. The window opens in
  steady state: the first ``slots`` requests (those that take the slots
  before it opens) stand for requests already in service, and their
  replies are the residual lives of ``output_tokens`` (what a request
  still has to serve at a random moment of a slot that is never idle), so
  they finish staggered, not in lockstep. The rest is drawn in blocks of
  ``backlog`` requests, about what a window admits, so every seed admits
  the same requests in its window, each in its own order (a prompt's
  length sets how many padded prefill dispatches its admission costs).

Length distributions (``prompt_tokens`` / ``output_tokens``):
``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
``{"dist": "uniform", "min", "max"}``.

Every seed gets the same (prompt, reply) pairs and gaps — the
distribution's quantiles at (i + 0.5) / n, per segment — and its own token
ids drawn over the whole vocabulary. So two seeds do the same amount of
work. ``order`` in the file says who orders the pairs and gaps:
``"seed"`` (the default), each seed its own order; ``"fixed"``, one order
drawn from a constant, the same for every seed, which then changes only
the ids. A tail over a few dozen requests (a p90 of first-token times)
follows the order, where long prompts bunch up; a rate over a window does
not.
"""

from __future__ import annotations

import dataclasses
import statistics
from typing import Any, Dict, List, Optional

import numpy as np


PAIRING = 0x9A1
# the fixed order: chat's busiest 8 s of committed tokens under it is the
# median of 300 seeds' orders
ORDER = 0x5EED5


@dataclasses.dataclass
class Request:
    index: int
    due_s: Optional[float]       # seconds after the window opens (open loop)
    prompt: np.ndarray           # int32 token ids
    max_new: int


def quantiles(spec: Dict[str, Any], n: int) -> np.ndarray:
    """The n sizes at quantiles (i + 0.5) / n of ``spec``, as ints."""
    q = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(p) for p in q])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = spec["min"] + q * (spec["max"] - spec["min"] + 1)
    else:
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    return np.clip(np.floor(v), spec["min"], spec["max"]).astype(np.int64)


def residual_quantiles(spec: Dict[str, Any], n: int,
                       grid: int = 4096) -> np.ndarray:
    """The n residual lives at quantiles (i + 0.5) / n: a reply length L
    drawn from ``spec`` (on ``grid`` quantiles) still has r >= 1 tokens to
    serve with P(r) proportional to P(L >= r)."""
    lengths = np.sort(quantiles(spec, grid))
    r = np.arange(1, int(lengths[-1]) + 1)
    weight = grid - np.searchsorted(lengths, r, side="left")
    cdf = np.cumsum(weight) / weight.sum()
    q = (np.arange(n) + 0.5) / n
    return r[np.searchsorted(cdf, q)]


def exp_gaps(rate: float, n: int) -> np.ndarray:
    """n inter-arrival gaps at the quantiles of an exponential(rate)."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def _arrivals(rng, rate: float, seconds: float, n: int) -> np.ndarray:
    """n arrival times in (0, seconds): n + 1 exponential gaps at their
    quantiles, in ``rng``'s order, scaled so that the (n + 1)-th arrival
    falls at ``seconds``."""
    t = np.cumsum(rng.permutation(exp_gaps(rate, n + 1)))
    return t[:n] * (seconds / t[n])


def schedule(traffic: Dict[str, Any], seed: int, seconds: float,
             vocab_size: int, slots: int) -> List[Request]:
    """The cell's requests for ``seed``, in submission order, for an
    engine of ``slots`` slots."""
    rng = np.random.default_rng(seed)
    order_by = traffic.get("order", "seed")
    if order_by not in ("seed", "fixed"):
        raise ValueError(f"unknown order {order_by!r}")
    order = np.random.default_rng(ORDER) if order_by == "fixed" else rng
    kind = traffic["kind"]
    out_spec = traffic["output_tokens"]
    if kind == "open_loop":
        arr = traffic["arrivals"]
        if arr["process"] != "poisson":
            raise ValueError(f"unknown arrival process {arr['process']!r}")
        rate, warm = arr["rate_per_s"], traffic["warm_s"]
        segments = [(-warm, warm), (0.0, seconds)]
        counts = [int(round(rate * length)) for _, length in segments]
        replies = [quantiles] * 2
    elif kind == "backlog":
        n, block = int(traffic["requests"]), int(traffic["backlog"])
        counts = [slots] + [min(block, n - i) for i in range(slots, n, block)]
        replies = [residual_quantiles] + [quantiles] * (len(counts) - 1)
        segments = [None] * len(counts)
    else:
        raise ValueError(f"unknown traffic kind {kind!r}")
    reqs: List[Request] = []
    for seg, n, reply in zip(segments, counts, replies):
        # prompt and reply lengths paired by one fixed shuffle (the same
        # pairs for every seed), the pairs then in ``order``
        pairs = np.stack([
            quantiles(traffic["prompt_tokens"], n),
            np.random.default_rng(PAIRING).permutation(reply(out_spec, n))],
            axis=1)
        prompt_len, out_len = order.permutation(pairs).T
        due: List[Optional[float]] = [None] * n
        if seg is not None:
            start, length = seg
            due = list(start + _arrivals(order, rate, length, n))
        for i in range(n):
            ids = rng.integers(0, vocab_size, int(prompt_len[i]),
                               dtype=np.int32)
            reqs.append(Request(len(reqs), due[i], ids, int(out_len[i])))
    return reqs
