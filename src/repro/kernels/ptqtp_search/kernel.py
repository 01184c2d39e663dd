"""Pallas TPU kernel: fused 9-candidate trit search (quantization-time hot loop).

For each element of a (R, G) group-row block held in VMEM, evaluates the
squared error of all 9 ternary pairs (c¹, c²) against w - α¹c¹ - α²c²
(paper Eq. 5 / Alg. 2 lines 14-21) with a fully unrolled compare-select chain
on the VPU — no gathers, no argmin reductions, 9 fused FMAs + selects per
element. Emits both planes in one pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# (0,0) first so exact ties prefer the sparse assignment (matches core/ref).
_CANDIDATES = (
    (0.0, 0.0),
    (0.0, 1.0),
    (0.0, -1.0),
    (1.0, 0.0),
    (-1.0, 0.0),
    (1.0, 1.0),
    (-1.0, -1.0),
    (1.0, -1.0),
    (-1.0, 1.0),
)


def _search_kernel(w_ref, a_ref, t1_ref, t2_ref):
    w = w_ref[...].astype(jnp.float32)          # (br, G)
    a = a_ref[...].astype(jnp.float32)          # (br, 2)
    a1 = a[:, 0:1]                              # (br, 1) broadcast over G
    a2 = a[:, 1:2]

    best_err = jnp.full_like(w, jnp.inf)
    best_t1 = jnp.zeros_like(w)
    best_t2 = jnp.zeros_like(w)
    for c1, c2 in _CANDIDATES:
        r = w - (a1 * c1 + a2 * c2)
        e = r * r
        take = e < best_err                      # strict: first candidate wins ties
        best_err = jnp.where(take, e, best_err)
        best_t1 = jnp.where(take, c1, best_t1)
        best_t2 = jnp.where(take, c2, best_t2)
    t1_ref[...] = best_t1
    t2_ref[...] = best_t2


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def ptqtp_search_pallas(
    w: jax.Array,
    alpha: jax.Array,
    *,
    block_rows: int = 256,
    interpret: bool,
):
    """Fused trit search. w: (R, G); alpha: (R, 2) -> (t1, t2) f32 (R, G)."""
    r, g = w.shape
    br = min(block_rows, r)
    pad = (-r) % br
    if pad:
        w = jnp.pad(w, ((0, pad), (0, 0)))
        alpha = jnp.pad(alpha, ((0, pad), (0, 0)))
    rp = w.shape[0]
    out = pl.pallas_call(
        _search_kernel,
        grid=(rp // br,),
        in_specs=[
            pl.BlockSpec((br, g), lambda i: (i, 0)),
            pl.BlockSpec((br, 2), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, g), lambda i: (i, 0)),
            pl.BlockSpec((br, g), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rp, g), jnp.float32),
            jax.ShapeDtypeStruct((rp, g), jnp.float32),
        ],
        interpret=interpret,
    )(w, alpha)
    t1, t2 = out
    if pad:
        t1, t2 = t1[:r], t2[:r]
    return t1, t2
