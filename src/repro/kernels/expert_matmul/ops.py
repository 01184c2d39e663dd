"""Public wrapper of the grouped expert matmul (``kernel.py``).

Backends, as ``ternary_matmul``'s: ``auto`` is the Pallas kernel
compiled on TPU and the XLA path elsewhere; ``pallas`` runs the kernel
(by the interpreter off TPU); ``xla`` (also what ``grouped`` and ``ref``
select) computes every row with its own expert's planes through the
grouped einsum of ``ternary_matmul``, one expert at a time. Rows of
tiles past the live count are undefined on every backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.expert_matmul.kernel import expert_matmul_pallas
from repro.kernels.ternary_matmul.ops import _grouped

# rows per expert tile: the bf16 sublane tile, so a decode step's few rows
# per expert pad to 16 and not to an MXU pass of 128
TILE_ROWS = 16


def n_tiles(assignments: int, experts: int, tile_rows: int = TILE_ROWS
            ) -> int:
    """Tiles that ``assignments`` rows sorted by expert can need: every
    expert's rows pad to whole tiles, at most one partial tile each."""
    return -(-assignments // tile_rows) + min(experts, assignments)


@functools.lru_cache(maxsize=None)
def block_n(n: int) -> int:
    """Output columns the kernel produces at once: the widest multiple of
    128 lanes up to 256 that divides n (all of n where none does)."""
    for bn in (256, 128):
        if n % bn == 0:
            return bn
    return n


def _xla(x, t1p, t2p, alpha, tile_expert, group_size, tile_rows):
    row_expert = jnp.repeat(tile_expert, tile_rows)

    def one(e, y):
        ye = _grouped(x, t1p[e], t2p[e], alpha[e], group_size)
        return jnp.where((row_expert == e)[:, None], ye, y)

    y0 = jnp.zeros((x.shape[0], t1p.shape[1]), jnp.float32)
    return jax.lax.fori_loop(0, t1p.shape[0], one, y0)


def expert_matmul(x, t1p, t2p, alpha, tile_expert, n_live, *,
                  group_size: int = 128, tile_rows: int = TILE_ROWS,
                  backend: str = "auto", out_dtype=None):
    """y[r] = x[r] @ Ŵ_{e(r)}ᵀ, row r of tile t under expert
    ``tile_expert[t]``. x (R, d) with R a multiple of ``tile_rows``;
    planes (E, n, d // 4) uint8 (int8 where the caller unpacked them,
    served by the XLA path); alpha (E, n, d // G, 2); n_live (1,) int32.
    Returns (R, n)."""
    if backend in (None, "auto"):
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    if t1p.dtype != jnp.uint8:
        backend = "xla"
    if backend == "pallas":
        e, n, db = t1p.shape
        # the orientation the TPU stores each stack in (kernel docstring):
        # transposing to it is a bitcast, not a copy
        planes_t = db % 128 != 0
        if planes_t:
            t1p, t2p = (jnp.swapaxes(t, 1, 2) for t in (t1p, t2p))
        alpha_t = alpha.transpose(0, 2, 3, 1).reshape(e, -1, n)
        y = expert_matmul_pallas(
            x, t1p, t2p, alpha_t, tile_expert, n_live, group_size=group_size,
            tile_rows=tile_rows, block_n=block_n(n), planes_t=planes_t,
            interpret=jax.default_backend() != "tpu")
    elif backend in ("xla", "grouped", "ref"):
        y = _xla(x, t1p, t2p, alpha, tile_expert, group_size, tile_rows)
    else:
        raise ValueError(f"unknown expert-matmul backend {backend!r}")
    return y.astype(out_dtype) if out_dtype is not None else y
