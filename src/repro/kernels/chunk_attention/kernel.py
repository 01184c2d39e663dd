"""Fused int8-KV flash chunk-prefill attention (Pallas, TPU target).

Grid (batch, kv-head, tile): one program per ``tile`` ring slots of one
(batch, kv head) pair. All G query heads that share the kv head ride in
one program — the GQA grouping — so a KV tile is read once per kv head,
not once per query head. The tile axis runs in order and carries the
online-softmax state (running max, sum, accumulator) in VMEM scratch; the
last tile folds the chunk's own keys in and writes the output. The
(G·L, cap) score block never exists: scores live as (G·L, tile).

Layout. The wrapper hands the kernel lane-dense views, so every block
obeys the TPU's (8, 128) rule at serving widths (hd a multiple of 128):
the cache (B, cap, KV, hd) is viewed as (B, cap, KV·hd) and the BlockSpec
takes lane block ``kv`` of width hd; int8 scales are transposed to
(KV·B, 1, cap) rows and applied to the scores and the softmax weights
(q·(s k) = s (q·k)), so no per-slot column ever needs a lane→sublane
relayout; positions come in both orientations (queries as a (G·L, 1)
column, keys as a (1, tile) row) so the mask is a plain broadcast. Those
(1, tile) rows need a tile that is a multiple of 128 or every slot;
``ops.lane_tile`` picks one for the compiled kernel.
Lengths (and the page table) ride in SMEM as scalar prefetch.

Ring wrap, sliding windows, and right-padding are all mask regions of the
same rule (see the package docstring): visible iff 0 <= qpos - kpos <
reach, ring slots additionally pos >= 0, chunk keys additionally
j < length.

The paged variant is the same kernel over a different index map: tile t
of row b is read from physical page ``table[b, t // tpp]`` (tile divides
page_size), so with matching tiles the two walks see the same (k, v,
mask) sequence and are bit-identical.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_NT = (((1,), (1,)), ((), ()))  # contract the last dim of both operands


def _online_update(q2, k, v, valid, ks, vs, m, l, acc):
    """One online-softmax step. q2: (G·L, hd) pre-scaled f32; k/v:
    (C, hd); valid: (G·L, C) bool; ks/vs: (1, C) per-key scales or None;
    carry m/l: (G·L, 1), acc: (G·L, hd)."""
    s = jax.lax.dot_general(q2, k.astype(jnp.float32), _NT,
                            preferred_element_type=jnp.float32)
    if ks is not None:
        s = s * ks
    s = jnp.where(valid, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    # explicit re-mask: when a row has seen nothing yet (m_new == NEG_INF)
    # the subtraction cancels and exp() would emit 1s for masked slots
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    pv = p if vs is None else p * vs
    acc = acc * alpha + jnp.dot(pv, v.astype(jnp.float32),
                                preferred_element_type=jnp.float32)
    l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    return m_new, l, acc


def _kernel(len_ref, *refs, paged: bool, scale: float, reach: int,
            scaled: bool):
    if paged:
        refs = refs[1:]  # the page table is only read by the index maps
    (q_ref, kn_ref, vn_ref, k_ref, ks_ref, v_ref, vs_ref, pb_ref, qp_ref,
     kp_ref, o_ref, m_sc, l_sc, acc_sc) = refs
    t = pl.program_id(2)
    length = len_ref[pl.program_id(0)]
    last = pl.num_programs(2) - 1

    @pl.when(t == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q2 = q_ref[0, 0].astype(jnp.float32) * scale             # (G·L, hd)
    qpos = qp_ref[0]                                         # (G·L, 1)
    pb = pb_ref[0]                                           # (1, tile)
    d = qpos - pb
    valid = (pb >= 0) & (d >= 0) & (d < reach)
    ks, vs = (ks_ref[0], vs_ref[0]) if scaled else (None, None)
    m, l, acc = _online_update(q2, k_ref[0], v_ref[0], valid, ks, vs,
                               m_sc[...], l_sc[...], acc_sc[...])
    m_sc[...], l_sc[...], acc_sc[...] = m, l, acc

    @pl.when(t == last)
    def _finish():
        # the chunk's own keys: one final (G·L, L) tile at activation
        # precision
        kpos = kp_ref[0]                                     # (1, L)
        jidx = jax.lax.broadcasted_iota(jnp.int32, kpos.shape, 1)
        dn = qpos - kpos
        valid_n = (jidx < length) & (dn >= 0) & (dn < reach)
        _, ln, accn = _online_update(q2, kn_ref[0], vn_ref[0], valid_n,
                                     None, None, m_sc[...], l_sc[...],
                                     acc_sc[...])
        o_ref[0, 0] = accn / jnp.maximum(ln, 1e-30)          # 0s if unseen


def _call(q, k_new, v_new, kc, ks, vc, vs, pos, positions, lengths, *,
          window, tile, cap, page_table, interpret):
    """Shared pallas_call. ``kc``/``vc`` are lane-dense (rows, slots,
    KV·hd) views, ``ks``/``vs`` (rows·KV, 1, slots) scale rows (None for
    float caches) and ``pos`` (rows, 1, slots); rows are batch rows for
    the ring and physical pages for the paged pool, where ``page_table``
    (B, n_pages) maps tile t of row b to its page."""
    b, kv, g, L, hd = q.shape
    n_tiles = cap // tile
    reach = min(window, cap) if window else cap
    scaled = ks is not None
    paged = page_table is not None

    rows = kc.shape[0]
    if paged:
        tpp = kc.shape[1] // tile                            # tiles per page

        def slot_block(i, t, tbl):
            return tbl[i, t // tpp], t % tpp
    else:
        def slot_block(i, t, tbl):
            return i, t

    def scale_index(i, j, t, _lens, tbl=None):
        row, off = slot_block(i, t, tbl)
        return j * rows + row, 0, off

    def kv_index(i, j, t, _lens, tbl=None):
        row, off = slot_block(i, t, tbl)
        return row, off, j

    def pos_index(i, j, t, _lens, tbl=None):
        row, off = slot_block(i, t, tbl)
        return row, 0, off

    def per_row(i, j, t, *_):
        return i, 0, 0

    if scaled:
        scale_spec = pl.BlockSpec((1, 1, tile), scale_index)
    else:  # float cache: 1-entry placeholders, never read
        ks = vs = jnp.ones((1, 1, 1), jnp.float32)
        scale_spec = pl.BlockSpec((1, 1, 1), lambda *_: (0, 0, 0))
    head_spec = pl.BlockSpec((1, 1, g * L, hd),
                             lambda i, j, t, *_: (i, j, 0, 0))
    new_spec = pl.BlockSpec((1, L, hd), lambda i, j, t, *_: (i, 0, j))
    kv_spec = pl.BlockSpec((1, tile, hd), kv_index)

    prefetch = (lengths,) + ((page_table,) if paged else ())
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b, kv, n_tiles),
        in_specs=[
            head_spec,                                       # q
            new_spec, new_spec,                              # k_new, v_new
            kv_spec, scale_spec, kv_spec, scale_spec,        # k, ks, v, vs
            pl.BlockSpec((1, 1, tile), pos_index),           # slot positions
            pl.BlockSpec((1, g * L, 1), per_row),            # query positions
            pl.BlockSpec((1, 1, L), per_row),                # chunk keys' pos
        ],
        out_specs=head_spec,
        scratch_shapes=[pltpu.VMEM((g * L, 1), jnp.float32),
                        pltpu.VMEM((g * L, 1), jnp.float32),
                        pltpu.VMEM((g * L, hd), jnp.float32)],
    )
    kern = functools.partial(_kernel, paged=paged, scale=hd ** -0.5,
                             reach=reach, scaled=scaled)
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, g * L, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="chunk_attention",
    )(*prefetch, q.reshape(b, kv, g * L, hd),
      k_new.reshape(b, L, kv * hd), v_new.reshape(b, L, kv * hd),
      kc, ks, vc, vs, pos,
      jnp.tile(positions, (1, g)).reshape(b, g * L, 1),     # row h·L + l
      positions.reshape(b, 1, L))
    return out.reshape(b, kv, g, L, hd)


def _scale_rows(scale):
    """(rows, slots, KV) per-slot scales → (KV·rows, 1, slots) lane rows,
    kv-major (row j·rows + r holds kv head j of ring row / page r)."""
    if scale is None:
        return None
    r, s, kv = scale.shape
    return scale.transpose(2, 0, 1).reshape(kv * r, 1, s).astype(jnp.float32)


def chunk_attention_paged_pallas(q, k_new, v_new, k_pool, k_scale, v_pool,
                                 v_scale, pos_pool, table, positions,
                                 lengths, *, window=None, tile: int,
                                 interpret: bool):
    """Paged Pallas chunk attention. q is (B, KV, G, L, hd) (grid layout);
    the public op transposes. Pools are (P, page_size, KV, hd) with
    (P, page_size, KV) scales (int8) or scales None (float); table is
    (B, n_pages) physical page ids. ``tile`` divides page_size. Returns
    (B, KV, G, L, hd) f32.
    """
    P, ps, kv, hd = k_pool.shape
    n_pages = table.shape[1]
    assert ps % tile == 0, (ps, tile)
    return _call(q, k_new, v_new, k_pool.reshape(P, ps, kv * hd),
                 _scale_rows(k_scale), v_pool.reshape(P, ps, kv * hd),
                 _scale_rows(v_scale), pos_pool.reshape(P, 1, ps),
                 positions, lengths, window=window, tile=tile,
                 cap=n_pages * ps, page_table=table.astype(jnp.int32),
                 interpret=interpret)


def chunk_attention_pallas(q, k_new, v_new, k_cache, k_scale, v_cache,
                           v_scale, pos_buf, positions, lengths, *,
                           window=None, tile: int, interpret: bool):
    """Pallas chunk attention. q here is (B, KV, G, L, hd) (grid layout);
    the public op transposes. ``tile`` divides cap. Returns
    (B, KV, G, L, hd) f32.
    """
    b, cap, kv, hd = k_cache.shape
    assert cap % tile == 0, (cap, tile)
    return _call(q, k_new, v_new, k_cache.reshape(b, cap, kv * hd),
                 _scale_rows(k_scale), v_cache.reshape(b, cap, kv * hd),
                 _scale_rows(v_scale), pos_buf.reshape(b, 1, cap),
                 positions, lengths, window=window, tile=tile, cap=cap,
                 page_table=None, interpret=interpret)
