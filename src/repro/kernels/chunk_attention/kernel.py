"""Fused int8-KV flash chunk-prefill attention (Pallas, TPU target).

Grid (batch, kv-head, tile): one program per ``tile`` ring slots of one
(batch, kv head) pair. All G query heads that share the kv head ride in
one program — the GQA grouping — so a KV tile is read once per kv head,
not once per query head. The tile axis runs in order and carries the
online-softmax state (running max, sum, accumulator) in VMEM scratch; the
last tile folds the chunk's own keys in and writes the output. The
(G·L, cap) score block never exists: scores live as (G·L, tile).

Layout. The kernel reads the ring where the model keeps it: one stacked
buffer per leaf for all layers of a scan, stored as the kernel reads it,
so no view or relayout is made per call (package docstring, "Ring
storage"). Keys and values are lane-dense (layers, B, cap, KV·hd) and the
BlockSpec takes lane block ``kv`` of width hd, which obeys the TPU's
(8, 128) rule at serving widths (hd a multiple of 128). Slot positions
(layers, rows, cap) and int8 scales (layers, KV, rows, cap), kv-head-major,
are read as row blocks of 8 rows (every row where the row count is not a
multiple of 8), of which the kernel takes its own (1, tile) row. So no
stored leaf has a unit or short axis that XLA could lay out another way
than the kernel, which would cost a relayout of the whole stack per call.
Scales apply to the scores and the softmax weights (q·(s k) = s (q·k)),
so no per-slot column ever needs a lane→sublane relayout. Query positions
come as a (G·L, 1) column, so the mask is a plain broadcast. Those
(1, tile) rows need a tile that is a multiple of 128 or every slot;
``ops.lane_tile`` picks one for the compiled kernel. The layer index
rides in SMEM as scalar prefetch, in one vector after the row lengths (the
page table, when paged, is a second), and every ring index map returns
block (layer, row, ...): the layer scan hands the kernel the whole stack
and no per-layer slice is ever copied out. (One vector, not two, also
keeps the call's operand count, 11 ring and 12 paged, which the
benchmark's trace reader matches as a fallback to the kernel's name.)

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


def _kernel(meta_ref, *refs, paged: bool, scale: float, reach: int,
            scaled: bool, row_block: int, tpp: int):
    if paged:
        tbl_ref, refs = refs[0], refs[1:]
    (q_ref, kn_ref, vn_ref, k_ref, ks_ref, v_ref, vs_ref, pb_ref, qp_ref,
     kp_ref, o_ref, m_sc, l_sc, acc_sc) = refs
    t = pl.program_id(2)
    length = meta_ref[pl.program_id(0)]
    last = pl.num_programs(2) - 1

    @pl.when(t == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q2 = q_ref[0, 0].astype(jnp.float32) * scale             # (G·L, hd)
    qpos = qp_ref[0]                                         # (G·L, 1)
    row = (tbl_ref[pl.program_id(0), t // tpp] if paged
           else pl.program_id(0))
    r = pl.ds(row % row_block, 1)                            # own row
    pb = pb_ref[0, r, :]                                     # (1, tile)
    d = qpos - pb
    valid = (pb >= 0) & (d >= 0) & (d < reach)
    ks, vs = ((ks_ref[0, 0, r, :], vs_ref[0, 0, r, :]) if scaled
              else (None, None))
    m, l, acc = _online_update(q2, k_ref[0, 0], v_ref[0, 0], valid, ks, vs,
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


def _call(q, k_new, v_new, kc, ks, vc, vs, pos, positions, lengths, layer, *,
          window, tile, cap, page_table, interpret):
    """Shared pallas_call over stacked storage: ``kc``/``vc`` (layers,
    rows, slots, KV·hd), ``ks``/``vs`` (layers, KV, rows, slots) (None
    for float caches), ``pos`` (layers, rows, slots); rows are batch rows
    for the ring and physical pages for the paged pool, where ``page_table``
    (B, n_pages) maps tile t of row b to its page. Every ring block is
    read at ``layer``, which rides in SMEM after the row lengths."""
    b, kv, g, L, hd = q.shape
    n_tiles = cap // tile
    reach = min(window, cap) if window else cap
    scaled = ks is not None
    paged = page_table is not None
    rows, slots = kc.shape[1:3]
    row_block = 8 if rows % 8 == 0 else rows                 # (8, 128) rule
    tpp = slots // tile if paged else 1                      # tiles per page

    if paged:
        def slot_block(i, t, tbl):
            return tbl[i, t // tpp], t % tpp
    else:
        def slot_block(i, t, tbl):
            return i, t

    def kv_index(i, j, t, meta, tbl=None):
        row, off = slot_block(i, t, tbl)
        return meta[b], row, off, j

    def pos_index(i, j, t, meta, tbl=None):
        row, off = slot_block(i, t, tbl)
        return meta[b], row // row_block, off

    def scale_index(i, j, t, meta, tbl=None):
        row, off = slot_block(i, t, tbl)
        return meta[b], j, row // row_block, off

    def per_row(i, j, t, *_):
        return i, 0, 0

    if scaled:
        scale_spec = pl.BlockSpec((1, 1, row_block, tile), scale_index)
    else:  # float cache: 1-entry placeholders, never read
        ks = vs = jnp.ones((1, 1, 1, 1), jnp.float32)
        scale_spec = pl.BlockSpec((1, 1, 1, 1), lambda *_: (0,) * 4)
    head_spec = pl.BlockSpec((1, 1, g * L, hd),
                             lambda i, j, t, *_: (i, j, 0, 0))
    new_spec = pl.BlockSpec((1, L, hd), lambda i, j, t, *_: (i, 0, j))
    kv_spec = pl.BlockSpec((1, 1, tile, hd), kv_index)

    # one SMEM vector: the B row lengths, then the layer index
    meta = jnp.concatenate([lengths.astype(jnp.int32),
                            jnp.reshape(layer, (1,)).astype(jnp.int32)])
    prefetch = (meta,) + ((page_table,) if paged else ())
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b, kv, n_tiles),
        in_specs=[
            head_spec,                                       # q
            new_spec, new_spec,                              # k_new, v_new
            kv_spec, scale_spec, kv_spec, scale_spec,        # k, ks, v, vs
            pl.BlockSpec((1, row_block, tile), pos_index),   # slot positions
            pl.BlockSpec((1, g * L, 1), per_row),            # query positions
            pl.BlockSpec((1, 1, L), per_row),                # chunk keys' pos
        ],
        out_specs=head_spec,
        scratch_shapes=[pltpu.VMEM((g * L, 1), jnp.float32),
                        pltpu.VMEM((g * L, 1), jnp.float32),
                        pltpu.VMEM((g * L, hd), jnp.float32)],
    )
    kern = functools.partial(_kernel, paged=paged, scale=hd ** -0.5,
                             reach=reach, scaled=scaled,
                             row_block=row_block, tpp=tpp)
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


def chunk_attention_paged_pallas(q, k_new, v_new, k_pool, k_scale, v_pool,
                                 v_scale, pos_pool, table, positions,
                                 lengths, layer, *, window=None, tile: int,
                                 interpret: bool):
    """Paged Pallas chunk attention. q is (B, KV, G, L, hd) (grid layout);
    the public op transposes. Pools are stacked storage (layers, P,
    page_size, KV·hd), scales (layers, KV, P, page_size) (int8) or None
    (float), positions (layers, P, page_size); table is (B, n_pages)
    physical page ids of layer ``layer``. ``tile`` divides page_size.
    Returns (B, KV, G, L, hd) f32.
    """
    ps = k_pool.shape[2]
    n_pages = table.shape[1]
    assert ps % tile == 0, (ps, tile)
    return _call(q, k_new, v_new, k_pool, k_scale, v_pool, v_scale, pos_pool,
                 positions, lengths, layer, window=window, tile=tile,
                 cap=n_pages * ps, page_table=table.astype(jnp.int32),
                 interpret=interpret)


def chunk_attention_pallas(q, k_new, v_new, k_cache, k_scale, v_cache,
                           v_scale, pos_buf, positions, lengths, layer, *,
                           window=None, tile: int, interpret: bool):
    """Pallas chunk attention. q here is (B, KV, G, L, hd) (grid layout);
    the public op transposes. The ring is stacked storage (layers, B, cap,
    KV·hd), scales (layers, KV, B, cap) or None, positions (layers, B,
    cap), read at ``layer``. ``tile`` divides cap. Returns
    (B, KV, G, L, hd) f32.
    """
    cap = k_cache.shape[2]
    assert cap % tile == 0, (cap, tile)
    return _call(q, k_new, v_new, k_cache, k_scale, v_cache, v_scale, pos_buf,
                 positions, lengths, layer, window=window, tile=tile, cap=cap,
                 page_table=None, interpret=interpret)
