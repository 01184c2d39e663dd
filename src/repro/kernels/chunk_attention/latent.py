"""Latent attention (MLA) over a stacked latent ring: the chunk-attention
contract for one shared key per token whose leading lanes are its value.

In DeepSeek-V2's latent attention every token caches one vector, its
normed latent c (``v_dim`` lanes) followed by its roped key part k_pe. The
query heads, absorbed into the latent space (q_nope · W_UKᵀ ‖ q_pe), all
score against that one vector, and the softmax weights multiply the
latent itself: multi-query attention of H heads against one key of C
lanes whose first ``v_dim`` lanes are the value. The output stays in the
latent space (the caller applies W_UV).

Op contract (``latent_attention``)::

  latent_attention(q, k_new, cache, pos_buf, positions, lengths, *,
                   scale, v_dim, layer=0, backend="auto", tile=None)
      -> (B, L, H, v_dim) float32

  q:        (B, L, H, C) absorbed queries; k_new (B, L, C) the chunk's own
            latents, scored before any cache write; cache (NL, B, cap, C)
            the stacked latent ring of NL layers, pos_buf (NL, B, cap) its
            absolute positions (-1 = empty), read at ``layer``; positions
            (B, L), lengths (B,) as in ``chunk_attention``; ``scale`` the
            softmax scale.

Masking is ``chunk_attention``'s without a window, from the same helpers
(``ref.reach_of``, ``ref.history_mask``, ``ref.chunk_mask``): causal,
never further back than the ring holds, ring slots with pos >= 0, chunk
keys j < length.

Backends: ``pallas`` (on TPU by ``auto``; by the interpreter elsewhere)
walks ring tiles of one row per program, grid (B, tiles), all H·L query
rows of the row in one block, reusing the chunk kernel's online-softmax
step; the latent tile is read once for both the scores and the values,
and a no-op row (length 0: a decode row not decoding, a prefill row with
nothing this chunk) reads no ring past its first tile.
``xla`` (``auto`` off TPU, and every other backend name) is the
materialized form: one softmax over (pre-write ring ∪ chunk).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.chunk_attention import ref as _ref
from repro.kernels.chunk_attention.kernel import NEG_INF, _online_update
from repro.kernels.chunk_attention.ops import _select_tile, lane_tile

# the latent tile is C = 576 lanes wide: a decode step (L = 1) walks the
# ring in tiles of this many query rows' worth, not in one ring-sized block
_MIN_ROWS = 16


def _kernel(meta_ref, q_ref, kn_ref, c_ref, pb_ref, qp_ref, kp_ref, o_ref,
            m_sc, l_sc, acc_sc, *, scale: float, reach: int, row_block: int,
            v_dim: int):
    t = pl.program_id(1)
    length = meta_ref[pl.program_id(0)]

    @pl.when(t == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q2 = q_ref[0].astype(jnp.float32) * scale                # (H·L, C)
    qpos = qp_ref[0]                                         # (H·L, 1)
    row = pl.ds(pl.program_id(0) % row_block, 1)

    @pl.when(length > 0)                                     # no-op rows skip
    def _tile():
        pb = pb_ref[0, row, :]
        d = qpos - pb                                        # (H·L, tile)
        valid = (pb >= 0) & (d >= 0) & (d < reach)
        kt = c_ref[0, 0]                                     # (tile, C)
        m, l, acc = _online_update(q2, kt, kt[:, :v_dim], valid, None, None,
                                   m_sc[...], l_sc[...], acc_sc[...])
        m_sc[...], l_sc[...], acc_sc[...] = m, l, acc

    @pl.when(t == pl.num_programs(1) - 1)
    def _finish():
        kpos = kp_ref[0]                                     # (1, L)
        jidx = jax.lax.broadcasted_iota(jnp.int32, kpos.shape, 1)
        dn = qpos - kpos
        valid_n = (jidx < length) & (dn >= 0) & (dn < reach)
        kn = kn_ref[0]                                       # (L, C)
        _, ln, accn = _online_update(q2, kn, kn[:, :v_dim], valid_n, None,
                                     None, m_sc[...], l_sc[...], acc_sc[...])
        o_ref[0] = accn / jnp.maximum(ln, 1e-30)             # 0s if unseen


@functools.partial(jax.jit, static_argnames=(
    "scale", "v_dim", "tile", "interpret"))
def latent_attention_pallas(q, k_new, cache, pos_buf, positions, lengths,
                            layer, *, scale: float, v_dim: int, tile: int,
                            interpret: bool):
    """Pallas latent attention; shapes as ``latent_attention``. ``tile``
    divides cap. Returns (B, L, H, v_dim) f32."""
    b, L, h, c = q.shape
    _, rows, cap, _ = cache.shape
    assert cap % tile == 0, (cap, tile)
    reach = _ref.reach_of(cap, None)
    row_block = 8 if rows % 8 == 0 else rows                 # (8, 128) rule
    meta = jnp.concatenate([lengths.astype(jnp.int32),
                            jnp.reshape(layer, (1,)).astype(jnp.int32)])
    per_row = lambda i, t, m: (i, 0, 0)
    # a no-op row (length 0) stays on its first tile: no further copies
    tile_of = lambda i, t, m: jnp.where(m[i] > 0, t, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, cap // tile),
        in_specs=[
            pl.BlockSpec((1, h * L, c), per_row),            # queries
            pl.BlockSpec((1, L, c), per_row),                # chunk latents
            pl.BlockSpec((1, 1, tile, c),                    # ring tile
                         lambda i, t, m: (m[b], i, tile_of(i, t, m), 0)),
            pl.BlockSpec((1, row_block, tile),
                         lambda i, t, m: (m[b], i // row_block,
                                          tile_of(i, t, m))),
            pl.BlockSpec((1, h * L, 1), per_row),            # query pos
            pl.BlockSpec((1, 1, L), per_row),                # chunk pos
        ],
        out_specs=pl.BlockSpec((1, h * L, v_dim), per_row),
        scratch_shapes=[pltpu.VMEM((h * L, 1), jnp.float32),
                        pltpu.VMEM((h * L, 1), jnp.float32),
                        pltpu.VMEM((h * L, v_dim), jnp.float32)],
    )
    kern = functools.partial(_kernel, scale=scale, reach=reach,
                             row_block=row_block, v_dim=v_dim)
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h * L, v_dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="latent_attention",
    )(meta, q.transpose(0, 2, 1, 3).reshape(b, h * L, c), k_new, cache,
      pos_buf, jnp.tile(positions, (1, h)).reshape(b, h * L, 1),
      positions.reshape(b, 1, L))
    return out.reshape(b, h, L, v_dim).transpose(0, 2, 1, 3)


def latent_attention_ref(q, k_new, cache, pos_buf, positions, lengths, *,
                         scale: float, v_dim: int, layer=0):
    """Materialized latent attention (one softmax over ring ∪ chunk)."""
    reach = _ref.reach_of(cache.shape[2], None)
    qf = q.astype(jnp.float32) * scale
    kc = cache[layer].astype(jnp.float32)                    # (B, cap, C)
    kn = k_new.astype(jnp.float32)
    s_hist = jnp.einsum("blhc,bsc->bhls", qf, kc)
    m_hist = _ref.history_mask(pos_buf[layer], positions, reach)
    s_hist = jnp.where(m_hist[:, None], s_hist, NEG_INF)
    s_self = jnp.einsum("blhc,bjc->bhlj", qf, kn)
    m_self = _ref.chunk_mask(positions, lengths, reach)
    s_self = jnp.where(m_self[:, None], s_self, NEG_INF)
    p = jax.nn.softmax(jnp.concatenate([s_hist, s_self], axis=-1), axis=-1)
    v_all = jnp.concatenate([kc[..., :v_dim], kn[..., :v_dim]], axis=1)
    return jnp.einsum("bhls,bsv->blhv", p, v_all)


def latent_tile(cap: int, L: int, compiled: bool) -> int:
    """Ring slots per grid step: the chunk op's tile for at least
    ``_MIN_ROWS`` query rows, lane-legal where the kernel is compiled."""
    t = _select_tile(cap, max(L, _MIN_ROWS))
    return lane_tile(cap, t) if compiled else t


def latent_attention(q, k_new, cache, pos_buf, positions, lengths, *,
                     scale: float, v_dim: int, layer=0,
                     backend: str = "auto", tile: Optional[int] = None):
    """Latent attention vs (pre-write ring ∪ chunk); module docstring."""
    if backend in (None, "auto"):
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    lengths = lengths.astype(jnp.int32)
    if backend != "pallas":
        return latent_attention_ref(q, k_new, cache, pos_buf, positions,
                                    lengths, scale=scale, v_dim=v_dim,
                                    layer=layer)
    interpret = jax.default_backend() != "tpu"
    t = (tile if tile is not None
         else latent_tile(cache.shape[2], q.shape[1], not interpret))
    return latent_attention_pallas(
        q, k_new, cache, pos_buf, positions.astype(jnp.int32), lengths,
        jnp.asarray(layer, jnp.int32), scale=float(scale), v_dim=v_dim,
        tile=t, interpret=interpret)
