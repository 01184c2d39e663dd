"""Pallas TPU kernel: fused unpack + grouped ternary matmul + per-group scale.

TPU adaptation of PTQTP's multiplication-free inference (DESIGN.md §2):
packed 2-bit trit-planes stream HBM→VMEM (0.5 B/weight instead of 2 B),
are unpacked with shifts/masks on the VPU, scaled by their group's α pair
and fed to the MXU in the activation dtype.

Numerics. Ŵ = α¹∘T¹ + α²∘T² is formed in f32 and rounded to the
activation dtype before the MXU, so a bf16 model multiplies by bf16
weights, as a dense bf16 checkpoint would (relative error 2^-9 per
weight, about 1.5e-3 in the output's norm). The XLA ``grouped`` path
keeps the trits exact and applies α in f32 after accumulation; the two
are compared under a stated limit, not bit for bit.

Layout. Byte b of a packed row holds trits 4b..4b+3 (field f = bits
2f..2f+1, see ``core/packing.py``), so unpacking a (bn, K) byte block
field by field yields four lane-dense (bn, K) planes: field f carries
weight columns 4b+f. Instead of interleaving those planes back into
column order (a minor-dim reshape the TPU compiler refuses), the wrapper
permutes the *activations* once per call, ``xp[f, :, b] = x[:, 4b+f]``,
and the kernel sums four matmuls:

    y = Σ_f  xp[f] @ (α¹ ∘ T¹_f + α² ∘ T²_f)ᵀ

Group g covers bytes [g·G/4, (g+1)·G/4) of a row, so the per-byte scales
α^p[:, b] = α^p[:, b // (G/4)] are an exact 0/1 matmul of the row's
(bn, 2·d/G) scale block with an expansion matrix built once in VMEM.

Every block spans the whole contraction (d/4 bytes), which is what the
TPU's block-shape rule allows for any d (d/4 need not be a multiple of
128): grid (m / bm, n / bn), one output block written once.
``block_m = m`` keeps a decode batch resident with no padding to MXU
tiles; ``ops._select_tiles`` picks the blocks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NT = (((1,), (1,)), ((), ()))  # contract the last dim of both operands
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _trits(packed_i32, field: int):
    """Field ``field`` of every packed byte as f32 trits: 1 → +1, 2 → −1."""
    f = (packed_i32 >> (2 * field)) & 0x3
    return ((f & 1) - (f >> 1)).astype(jnp.float32)


def _ternary_kernel(x_ref, t1_ref, t2_ref, a_ref, o_ref, e_ref, *, gb):
    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _build_expansion():
        # e[p][2g + p, b] = 1 iff byte b lies in group g: scale column
        # 2g + p of a row's (α¹, α²) pairs lands on that group's bytes
        r = jax.lax.broadcasted_iota(jnp.int32, e_ref.shape[1:], 0)
        b = jax.lax.broadcasted_iota(jnp.int32, e_ref.shape[1:], 1)
        lo = (r >> 1) * gb
        in_group = (b >= lo) & (b < lo + gb)
        for p in range(2):
            e_ref[p] = jnp.where(in_group & ((r & 1) == p), 1.0, 0.0)

    a = a_ref[...].astype(jnp.float32)                  # (bn, 2·ng)
    hi = jax.lax.Precision.HIGHEST                      # exact α copies
    a1 = jnp.dot(a, e_ref[0], precision=hi,
                 preferred_element_type=jnp.float32)    # (bn, d/4)
    a2 = jnp.dot(a, e_ref[1], precision=hi,
                 preferred_element_type=jnp.float32)
    p1 = t1_ref[...].astype(jnp.int32)
    p2 = t2_ref[...].astype(jnp.int32)
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for f in range(4):
        w = (_trits(p1, f) * a1 + _trits(p2, f) * a2).astype(x_ref.dtype)
        acc += jax.lax.dot_general(x_ref[f], w, _NT,
                                   preferred_element_type=jnp.float32)
    o_ref[...] = acc


@functools.partial(
    jax.jit,
    static_argnames=("group_size", "block_m", "block_n", "interpret"),
)
def ternary_matmul_pallas(
    x: jax.Array,
    t1p: jax.Array,
    t2p: jax.Array,
    alpha: jax.Array,
    *,
    group_size: int,
    block_m: int,
    block_n: int,
    interpret: bool,
) -> jax.Array:
    """y = x @ Ŵᵀ from packed trit-planes.

    Args:
      x:     (m, d) activations (f32/bf16); the MXU runs in this dtype.
      t1p:   (n, d // 4) uint8 packed plane 1.
      t2p:   (n, d // 4) uint8 packed plane 2.
      alpha: (n, d // group_size, 2) float scales.
      block_m / block_n: must divide m / n.
    Returns:
      (m, n) f32.
    """
    m, d = x.shape
    n = t1p.shape[0]
    g = group_size
    assert d % g == 0 and g % 4 == 0, (d, g)
    assert t1p.shape == (n, d // 4)
    assert alpha.shape == (n, d // g, 2)
    bm, bn = block_m, block_n
    assert m % bm == 0 and n % bn == 0, (m, bm, n, bn)
    ng, db = d // g, d // 4

    xp = x.reshape(m, db, 4).transpose(2, 0, 1)         # (4, m, d/4)
    a = alpha.reshape(n, 2 * ng)                        # free: row-major
    kernel = functools.partial(_ternary_kernel, gb=g // 4)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((4, bm, db), lambda i, j: (0, i, 0)),
            pl.BlockSpec((bn, db), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, db), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, 2 * ng), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((2, 2 * ng, db), jnp.float32)],
        # the expansion scratch is built at the first grid step and read by
        # every later one, so the grid must run in order on one core; a
        # d = 8960 prefill block needs ~20 MB of VMEM, past the 16 MB
        # default scoped limit (a v5e core has 128 MiB)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="ternary_matmul",
    )(xp, t1p, t2p, a)
