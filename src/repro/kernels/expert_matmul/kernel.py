"""Pallas TPU kernel: grouped ternary matmul over rows sorted by expert.

The routed experts of an MoE layer are one stack of trit-planes, (E,
d_out, d_in/4) bytes per plane and (E, d_out, d_in/G, 2) scales. The
caller sorts a dispatch's (token, expert) assignments by expert into row
tiles of ``tile_rows``, each tile holding rows of one expert only (an
expert's last tile is zero-padded), and passes the tile → expert map and
the number of live tiles as scalar prefetch. Grid step i multiplies tile
i by its expert's planes: the planes' block index is that expert, so
consecutive tiles of one expert read its planes once, and an expert no
row was routed to is never read. Steps past the live count run nothing,
and their blocks are those of the last live tile, so they start no copy
and write nothing back: the work follows the routed rows, not the
worst-case tile count the buffer is sized for.

Per tile the arithmetic is ``ternary_matmul``'s (``kernels/
ternary_matmul/kernel.py``): activations permuted once per call into four
byte fields, each expert plane unpacked field by field on the VPU,
scaled by its group's α pair through an exact 0/1 expansion matmul, and
fed to the MXU in the activation dtype, accumulated in f32. The output
columns are produced in chunks of ``block_n`` so that the unpacked
planes of one chunk, not of the whole matrix, live in VMEM at once.

Layout. The kernel reads each stack in the order the TPU stores it, so
that no stack is relaid out (a stack of every layer's experts, copied
whole, would not fit beside the model): the TPU keeps the d_out axis
minor where the trailing axes are short or not lane-aligned. So the
scales come as (E, 2·d_in/G, d_out) (``ops`` transposes them, a bitcast
of their stored layout), and planes whose d_in/4 is not a multiple of
128 come as (E, d_in/4, d_out) (``planes_t``); the expansion then runs
as (d_in/4, 2·d_in/G) @ (2·d_in/G, bn) and each field as x_f @ Ŵ_fᵀ in
the stored orientation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ternary_matmul.kernel import _VMEM_LIMIT_BYTES, _trits

_HI = jax.lax.Precision.HIGHEST                         # exact α copies


def _expert_kernel(te_ref, nl_ref, x_ref, t1_ref, t2_ref, a_ref, o_ref,
                   e_ref, *, gb, block_n, planes_t):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _build_expansion():
        # e[p][b, 2g + p] = 1 iff byte b lies in group g: scale row 2g + p
        # of the transposed (α¹, α²) pairs lands on that group's bytes
        b = jax.lax.broadcasted_iota(jnp.int32, e_ref.shape[1:], 0)
        r = jax.lax.broadcasted_iota(jnp.int32, e_ref.shape[1:], 1)
        lo = (r >> 1) * gb
        in_group = (b >= lo) & (b < lo + gb)
        for p in range(2):
            e_ref[p] = jnp.where(in_group & ((r & 1) == p), 1.0, 0.0)

    @pl.when(i < nl_ref[0])
    def _tile():
        for c in range(o_ref.shape[1] // block_n):
            cols = pl.ds(c * block_n, block_n)
            a = a_ref[0, :, cols].astype(jnp.float32)   # (2·ng, bn)
            a1 = jnp.dot(e_ref[0], a, precision=_HI,
                         preferred_element_type=jnp.float32)  # (d/4, bn)
            a2 = jnp.dot(e_ref[1], a, precision=_HI,
                         preferred_element_type=jnp.float32)
            if planes_t:                                # (d/4, bn) bytes
                p1 = t1_ref[0, :, cols].astype(jnp.int32)
                p2 = t2_ref[0, :, cols].astype(jnp.int32)
            else:                                       # (bn, d/4) bytes
                p1 = t1_ref[0, cols, :].astype(jnp.int32).T
                p2 = t2_ref[0, cols, :].astype(jnp.int32).T
            acc = jnp.zeros((o_ref.shape[0], block_n), jnp.float32)
            for f in range(4):
                w = (_trits(p1, f) * a1 + _trits(p2, f) * a2).astype(
                    x_ref.dtype)                        # Ŵ_fᵀ (d/4, bn)
                acc += jnp.dot(x_ref[f], w,
                               preferred_element_type=jnp.float32)
            o_ref[:, cols] = acc


@functools.partial(
    jax.jit,
    static_argnames=("group_size", "tile_rows", "block_n", "planes_t",
                     "interpret"))
def expert_matmul_pallas(x, t1p, t2p, alpha_t, tile_expert, n_live, *,
                         group_size: int, tile_rows: int, block_n: int,
                         planes_t: bool, interpret: bool):
    """y[r] = x[r] @ Ŵ_{e(r)}ᵀ for rows sorted by expert.

    Args:
      x:           (R, d) activations, R = n_tiles · tile_rows, tile t
                   holding rows of expert ``tile_expert[t]`` only.
      t1p, t2p:    packed planes of the stack, uint8: (E, n, d // 4), or
                   (E, d // 4, n) with ``planes_t``.
      alpha_t:     (E, 2 · d // group_size, n) scales, row 2g + p holding
                   α^(p+1) of group g.
      tile_expert: (n_tiles,) int32, every entry a real expert in [0, E):
                   the planes of a dead tile's expert are still the block
                   its step names, and the TPU does not bounds-check a
                   block index. n_live: (1,) int32 live tiles, the first
                   ones. Rows of tiles past n_live come back undefined.
      block_n:     output columns per chunk; divides n.
    Returns:
      (R, n) f32.
    """
    r, d = x.shape
    e, n = alpha_t.shape[0], alpha_t.shape[2]
    g, tm, db = group_size, tile_rows, d // 4
    ng = d // g
    assert d % g == 0 and g % 4 == 0, (d, g)
    assert t1p.shape == ((e, db, n) if planes_t else (e, n, db)), t1p.shape
    assert alpha_t.shape == (e, 2 * ng, n), alpha_t.shape
    assert r % tm == 0 and n % block_n == 0, (r, tm, n, block_n)

    def live(i, nl):
        return jnp.minimum(i, jnp.maximum(nl[0] - 1, 0))

    def expert(i, te, nl):
        return te[live(i, nl)], 0, 0

    xp = x.reshape(r, db, 4).transpose(2, 0, 1)         # (4, R, d/4)
    plane_block = (1, db, n) if planes_t else (1, n, db)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(r // tm,),
        in_specs=[
            pl.BlockSpec((4, tm, db), lambda i, te, nl: (0, live(i, nl), 0)),
            pl.BlockSpec(plane_block, expert),
            pl.BlockSpec(plane_block, expert),
            pl.BlockSpec((1, 2 * ng, n), expert),
        ],
        out_specs=pl.BlockSpec((tm, n), lambda i, te, nl: (live(i, nl), 0)),
        scratch_shapes=[pltpu.VMEM((2, db, 2 * ng), jnp.float32)],
    )
    kernel = functools.partial(_expert_kernel, gb=g // 4, block_n=block_n,
                               planes_t=planes_t)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, n), jnp.float32),
        # the expansion scratch is built at the first step, and a step past
        # the live count must not write its (shared) output block: the grid
        # runs in order on one core
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="expert_matmul",
    )(tile_expert.astype(jnp.int32), n_live.astype(jnp.int32), xp, t1p, t2p,
      alpha_t)
