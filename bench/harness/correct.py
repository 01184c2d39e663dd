"""What decides ``correct``: served tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample of
the requests that were served is run through the configuration's
reference: the longest in full, and ``sample_requests`` others drawn from
the seed, each up to its first ``tokens_per_request`` served tokens, so
that the sample spans many of the slots the window kept busy. Each runs
as one full forward pass over its prompt with its served tokens appended
(teacher forcing), in float32, ``PASS_ROWS`` sequences to a pass. At each
served token the reference's best logit minus the logit of the token the
program served is that token's gap; the widest gap is compared with the
configuration's limit. Greedy decoding serves the argmax of the program's
own logits, so a sound program reads a gap no larger than its logit error
where two logits nearly tie, and a wrong token reads the gap it lost by.

The control is the reference computed in float8 (e4m3, one scale per
tensor): at each position the token that float8 puts first, and its gap
in the float32 reference.

A configuration compares one or both statistics of the gaps (``STATS``),
each with its own limit in its ``correct`` block: the widest gap, or,
where the widest gap of sound runs swings too far to sit three times
below the control's, the mean gap over the compared tokens.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

ROW_BLOCK = 256
PAD_TO = 512
PASS_ROWS = 8            # sequences per reference pass, to bound its memory
STATS = {
    "max_logit_gap": lambda g: float(g.max()),
    "mean_logit_gap": lambda g: float(g.mean()),
}


def stats(gaps: np.ndarray) -> Dict[str, float]:
    """Every statistic of ``STATS`` over the gaps (nan where none)."""
    return {k: (f(gaps) if gaps.size else float("nan"))
            for k, f in STATS.items()}


def sample(records: Sequence, seed: int, requests: int,
           per_request: int) -> List[Tuple]:
    """(record, served tokens compared) pairs: the longest served request
    in full, then ``requests`` others drawn from the seed, each up to
    ``per_request`` tokens."""
    served = [r for r in records if r.tokens]
    if not served:
        return []
    longest = max(served, key=lambda r: (len(r.tokens), -r.req.index))
    others = [r for r in served if r is not longest]
    rng = np.random.default_rng([seed, 0x5EED])
    picked = [others[i] for i in rng.permutation(len(others))[:requests]]
    return ([(longest, len(longest.tokens))]
            + [(r, min(per_request, len(r.tokens))) for r in picked])


@functools.partial(jax.jit, static_argnames=("mm",))
def _block(h, h8, served, w, *, mm):
    """Per row: the reference's best logit minus its logit of the served
    token, and (with ``h8``) of the token the float8 control puts first."""
    logits = mm(h, w, False)
    best = jnp.max(logits, -1)
    gap = best - jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
    if h8 is None:
        return gap, gap
    pick = jnp.argmax(mm(h8, w, True), -1)
    return gap, best - jnp.take_along_axis(logits, pick[:, None], -1)[:, 0]


def served_gaps(ref, c: Dict, seeded, chosen: Sequence[Tuple],
                control: bool = False) -> Dict[str, np.ndarray]:
    """Gaps of the compared served tokens of ``chosen`` ((record, n)
    pairs; with ``control``, also of the tokens the float8 reference puts
    first); the reference draws its weights from ``seeded``
    (``weights.Seeded``)."""
    w = ref.head(c, seeded)
    out = {"gaps": [], "control_gaps": []}
    by_len = sorted(chosen, key=lambda rn: len(rn[0].req.prompt) + rn[1])
    for lo in range(0, len(by_len), PASS_ROWS):
        _compare(ref, c, seeded, w, by_len[lo:lo + PASS_ROWS], control,
                 out)
    return {k: (np.concatenate(v) if v else np.zeros((0,)))
            for k, v in out.items()}


def _compare(ref, c: Dict, seeded, w, chosen: Sequence[Tuple],
             control: bool, out: Dict[str, List]) -> None:
    """One reference pass over ``chosen``; appends their gaps to ``out``."""
    seqs = [np.concatenate([r.req.prompt,
                            np.asarray(r.tokens[:n - 1], np.int32)])
            for r, n in chosen]
    t_pad = PAD_TO * math.ceil(max(len(s) for s in seqs) / PAD_TO)
    toks = np.zeros((PASS_ROWS, t_pad), np.int32)   # one shape per length
    for b, s in enumerate(seqs):
        toks[b, :len(s)] = s
    hidden = {False: ref.final_hidden(c, seeded, toks)}
    if control:
        hidden[True] = ref.final_hidden(c, seeded, toks, fp8=True)

    for b, (r, n) in enumerate(chosen):
        p = len(r.req.prompt)
        served = np.asarray(r.tokens[:n], np.int32)
        for i in range(0, n, ROW_BLOCK):
            j = min(n, i + ROW_BLOCK)
            pad = ROW_BLOCK - (j - i)
            sl = slice(p - 1 + i, p - 1 + j)
            h = jnp.pad(hidden[False][b, sl], ((0, pad), (0, 0)))
            h8 = (jnp.pad(hidden[True][b, sl], ((0, pad), (0, 0)))
                  if control else None)
            s = jnp.asarray(np.pad(served[i:j], (0, pad)))
            gap, cgap = _block(h, h8, s, w, mm=ref.mm)
            out["gaps"].append(np.asarray(gap)[:j - i])
            if control:
                out["control_gaps"].append(np.asarray(cgap)[:j - i])
