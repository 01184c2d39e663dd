"""Public wrapper for flash chunk-prefill attention over the ring cache.

Backends (see the package docstring for the full contract):
  * ``auto``         — ``pallas`` on TPU, ``stream`` elsewhere.
  * ``pallas``       — the fused TPU kernel, compiled on TPU and run by the
                       Pallas interpreter elsewhere (the platform alone
                       decides).
  * ``stream``       — XLA fallback: a jitted ``fori_loop`` over
                       fixed-size ring tiles carrying running (max, sum,
                       acc) online-softmax state; peak attention
                       allocation O(L·tile), the ring sliced and
                       dequantized one int8 tile at a time.
  * ``materialized`` — the pre-PR-5 full-block path (``ref.py``), kept as
                       the measured baseline and parity oracle.

Tile selection: one tile is sized so the live score block stays near
``_TILE_ELEMS`` elements per (kv-head, group) — so decode (L = 1) gets a
single full-ring tile (no loop overhead on the hot path) while a 64-token
prefill chunk against a 32k ring walks 128 tiles. Tiles must divide cap
exactly (same rule as the ternary-matmul grid). The compiled Pallas kernel
further rounds the tile up to a lane-legal one (``lane_tile``); the
interpreter walks the tile as given.

``tracked_block_bytes`` / ``peak_tracked_bytes`` expose the analytic score
-block footprint — the number the long-context benchmark reports and the
O(L·tile) test asserts (trace-time recording survives jit caching because
the figure is a pure function of static shapes).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.chunk_attention import ref as _ref
from repro.kernels.chunk_attention.kernel import (chunk_attention_paged_pallas,
                                                  chunk_attention_pallas)
from repro.kernels.chunk_attention.ref import NEG_INF, gather_pages, reach_of

DEFAULT_BACKEND = "auto"
# target elements per (G·L, tile) score block — balances scan trip count
# against peak allocation; at L=1 (decode) any cap <= 8192 is one tile.
_TILE_ELEMS = 8192


def resolve_chunk_backend(backend: Optional[str] = None,
                          platform: Optional[str] = None) -> str:
    """Map 'auto'/None to the fastest backend for the current platform."""
    if backend in (None, "auto"):
        platform = platform or jax.default_backend()
        return "pallas" if platform == "tpu" else "stream"
    return backend


@functools.lru_cache(maxsize=None)
def _select_tile(cap: int, L: int) -> int:
    """Largest divisor of cap with L·tile <= _TILE_ELEMS.

    Tiles must divide cap exactly (no padded ring reads). A cap with no
    useful divisor structure (e.g. prime) would degenerate into a
    per-slot scan, so such caps take the whole ring as one tile — correct,
    just without the O(L·tile) bound; engine capacities are powers of two
    in practice.
    """
    target = max(1, _TILE_ELEMS // max(L, 1))
    if cap <= target:
        return cap
    best = 1
    i = 1
    while i * i <= cap:
        if cap % i == 0:
            for d in (i, cap // i):
                if best < d <= target:
                    best = d
        i += 1
    return best if best >= min(target, 64) else cap


@functools.lru_cache(maxsize=None)
def paged_tile(page_size: int, L: int) -> int:
    """Largest divisor of page_size with L·tile <= _TILE_ELEMS.

    Paged tiles must divide the page (one tile never spans two physical
    pages — the gather stays a single dynamic slice), the paged analogue of
    the divide-cap rule above. Page sizes are powers of two in practice, so
    this is page_size itself until L·page_size crosses _TILE_ELEMS.
    """
    target = max(1, _TILE_ELEMS // max(L, 1))
    if page_size <= target:
        return page_size
    best = 1
    i = 1
    while i * i <= page_size:
        if page_size % i == 0:
            for d in (i, page_size // i):
                if best < d <= target:
                    best = d
        i += 1
    return best


@functools.lru_cache(maxsize=None)
def lane_tile(slots: int, tile: int) -> int:
    """The tile a compiled Pallas kernel walks over ``slots`` ring slots
    (or page entries): the kernel reads positions and int8 scales as
    (1, tile) rows, which the TPU accepts only for a multiple of 128 lanes
    or all of ``slots``. ``tile`` itself when legal, else the smallest
    legal divisor of ``slots`` above it (the score block grows to at most
    (G·L, 128) per step).
    """
    if tile % 128 == 0 or tile == slots:
        return tile
    for t in range(128 * -(-tile // 128), slots, 128):
        if slots % t == 0:
            return t
    return slots


def tracked_block_bytes(b: int, kv: int, g: int, L: int, cap: int, *,
                        backend: str, tile: Optional[int] = None) -> int:
    """Analytic peak f32 score-block bytes for one op call."""
    if backend == "materialized":
        width = cap + L
    else:
        width = tile if tile is not None else _select_tile(cap, L)
    return 4 * b * kv * g * L * width


_TRACK = {"peak_bytes": 0}


def reset_tracking() -> None:
    _TRACK["peak_bytes"] = 0


def peak_tracked_bytes() -> int:
    """Largest score-block footprint recorded at trace time since the last
    ``reset_tracking()`` (0 if every call since hit a cached jit trace —
    use ``tracked_block_bytes`` for shape-analytic accounting)."""
    return _TRACK["peak_bytes"]


def _stream_update(qf, carry, k, v, valid):
    """One online-softmax accumulation step, shared by the contiguous-ring
    and paged stream paths (one implementation ⇒ the two walks are
    bit-identical whenever they see the same logical tile sequence).

    qf: (B, KV, G, L, hd) pre-scaled f32 queries; k/v: (B, C, KV, hd) f32;
    valid: (B, L, C) bool; carry (m, l, acc).
    """
    m, l, acc = carry
    s = jnp.einsum("bkgld,bckd->bkglc", qf, k)               # (B,KV,G,L,C)
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.where(valid[:, None, None],
                  jnp.exp(s - m_new[..., None]), 0.0)
    acc = acc * alpha[..., None] + jnp.einsum("bkglc,bckd->bkgld", p, v)
    l = l * alpha + jnp.sum(p, axis=-1)
    return m_new, l, acc


def _stream_carry0(b, kv, g, L, hd):
    return (jnp.full((b, kv, g, L), NEG_INF, jnp.float32),
            jnp.zeros((b, kv, g, L), jnp.float32),
            jnp.zeros((b, kv, g, L, hd), jnp.float32))


def _stream_finish(qf, carry, k_new, v_new, positions, lengths, reach):
    """Fold the chunk's own keys in as the final tile and normalize."""
    m, l, acc = _stream_update(qf, carry, k_new.astype(jnp.float32),
                               v_new.astype(jnp.float32),
                               _ref.chunk_mask(positions, lengths, reach))
    out = acc / jnp.maximum(l, 1e-30)[..., None]             # 0s if unseen
    return out.transpose(0, 3, 1, 2, 4)                      # (B,L,KV,G,hd)


def _slot_slice(a, layer, off, tile: int, axis: int):
    """Slots [off, off + tile) on ``axis`` of layer ``layer`` of a stacked
    storage leaf: one in-place ``dynamic_slice``, no copy of the layer.
    None passes through (the scales of a float ring)."""
    if a is None:
        return None
    axis %= a.ndim
    start = [0] * a.ndim
    start[0], start[axis] = layer, off
    size = list(a.shape)
    size[0], size[axis] = 1, tile
    return jax.lax.dynamic_slice(a, start, size)[0]


def _tile_update(qf, carry, k, ks, v, vs, pt, positions, reach, kv):
    """Dequantize one storage-layout tile and fold it into the carry.
    k/v (B, tile, KV·hd), ks/vs (KV, B, tile) or None, pt (B, tile) — the
    same step for the ring and paged walks."""
    sc = lambda a: None if a is None else _ref.scale_logical(a)
    k = _ref._deq(_ref.kv_logical(k, kv), sc(ks))         # (B, tile, KV, hd)
    v = _ref._deq(_ref.kv_logical(v, kv), sc(vs))
    d = positions[:, :, None] - pt[:, None, :]               # (B, L, tile)
    valid = (pt[:, None, :] >= 0) & (d >= 0) & (d < reach)
    return _stream_update(qf, carry, k, v, valid)


def _stream(q, k_new, v_new, k_cache, k_scale, v_cache, v_scale, pos_buf,
            positions, lengths, layer, *, window, tile):
    """Online-softmax loop over ring tiles; chunk keys fold in last.

    Tiles are ``dynamic_slice``d out of the stacked ring at ``layer`` in
    place — no upfront slice, reshape or transpose copy of the cache,
    which would be a second full pass over exactly the HBM bytes this path
    exists to not touch twice.
    """
    b, L, kv, g, hd = q.shape
    cap = k_cache.shape[2]
    reach = reach_of(cap, window)
    scale = hd ** -0.5
    qf = q.astype(jnp.float32).transpose(0, 2, 3, 1, 4) * scale  # (B,KV,G,L,hd)

    def ring_tile(i, carry):
        kvt = lambda a: _slot_slice(a, layer, i * tile, tile, 2)
        row = lambda a: _slot_slice(a, layer, i * tile, tile, -1)
        return _tile_update(qf, carry, kvt(k_cache), row(k_scale),
                            kvt(v_cache), row(v_scale), row(pos_buf),
                            positions, reach, kv)

    n_tiles = cap // tile
    carry0 = _stream_carry0(b, kv, g, L, hd)
    if n_tiles == 1:  # decode fast path: no loop machinery for one tile
        carry = ring_tile(0, carry0)
    else:
        carry = jax.lax.fori_loop(0, n_tiles, ring_tile, carry0)
    return _stream_finish(qf, carry, k_new, v_new, positions, lengths, reach)


def _stream_paged(q, k_new, v_new, k_pool, k_scale, v_pool, v_scale,
                  pos_pool, table, positions, lengths, layer, *, window,
                  tile):
    """Paged stream path: the same online-softmax walk over *logical* tiles,
    each gathered through the page table (tile divides page_size, so one
    tile never spans two physical pages). Tile i covers logical slots
    [i·tile, (i+1)·tile) of the virtual ring ``gather_pages`` defines; with
    equal tile sizes the (k, v, valid) sequence matches the contiguous-ring
    walk exactly, so the two are bit-identical per backend.
    """
    b, L, kv, g, hd = q.shape
    ps = k_pool.shape[2]
    n_pages = table.shape[1]
    cap = n_pages * ps
    reach = reach_of(cap, window)
    scale = hd ** -0.5
    qf = q.astype(jnp.float32).transpose(0, 2, 3, 1, 4) * scale
    tpp = ps // tile                                         # tiles per page

    def page_tile(i, carry):
        pidx = i // tpp
        off = (i % tpp) * tile
        phys = jax.lax.dynamic_index_in_dim(table, pidx, axis=1,
                                            keepdims=False)  # (B,)

        def sl(pool, axis, rows=0):
            if pool is None:
                return None
            pages = jnp.take(jax.lax.dynamic_index_in_dim(
                pool, layer, 0, keepdims=False), phys, axis=rows)
            return jax.lax.dynamic_slice_in_dim(pages, off, tile, axis=axis)

        return _tile_update(qf, carry, sl(k_pool, 1), sl(k_scale, -1, 1),
                            sl(v_pool, 1), sl(v_scale, -1, 1),
                            sl(pos_pool, -1), positions, reach, kv)

    n_tiles = n_pages * tpp
    carry0 = _stream_carry0(b, kv, g, L, hd)
    if n_tiles == 1:
        carry = page_tile(0, carry0)
    else:
        carry = jax.lax.fori_loop(0, n_tiles, page_tile, carry0)
    return _stream_finish(qf, carry, k_new, v_new, positions, lengths, reach)


def chunk_attention(q, k_new, v_new, k_cache, k_scale, v_cache, v_scale,
                    pos_buf, positions, lengths, *, layer=0,
                    window: Optional[int] = None,
                    backend: str = DEFAULT_BACKEND,
                    tile: Optional[int] = None):
    """Chunk-prefill attention vs (pre-write ring ∪ in-chunk keys).

    Shapes/masks: package docstring. The ring is the stacked storage of
    every layer of a scan; ``layer`` (an int or a traced int32 scalar)
    picks the one read. Returns (B, L, KV, G, hd) float32.
    ``k_scale``/``v_scale`` are None for float (bf16/f32) ring caches.
    """
    b, L, kv, g, hd = q.shape
    cap = k_cache.shape[2]
    backend = resolve_chunk_backend(backend)
    t = tile if tile is not None else _select_tile(cap, L)
    t = min(t, cap)
    while cap % t:  # tiles must divide cap exactly — a remainder tile would
        t -= 1      # silently drop ring slots from the visible set
    interpret = jax.default_backend() != "tpu"
    if backend == "pallas" and not interpret:
        t = lane_tile(cap, t)
    _TRACK["peak_bytes"] = max(
        _TRACK["peak_bytes"],
        tracked_block_bytes(b, kv, g, L, cap, backend=backend, tile=t))
    if backend == "materialized":
        return _ref.chunk_attention_ref(
            q, k_new, v_new,
            *_ref.ring_layer(k_cache, k_scale, v_cache, v_scale, pos_buf,
                             layer, kv),
            positions, lengths, window=window)
    if backend == "stream":
        return _stream(q, k_new, v_new, k_cache, k_scale, v_cache, v_scale,
                       pos_buf, positions, lengths, layer, window=window,
                       tile=t)
    if backend == "pallas":
        out = chunk_attention_pallas(
            q.transpose(0, 2, 3, 1, 4), k_new, v_new, k_cache, k_scale,
            v_cache, v_scale, pos_buf, positions,
            lengths.astype(jnp.int32), layer, window=window,
            tile=t, interpret=interpret)
        return out.transpose(0, 3, 1, 2, 4)
    raise ValueError(f"unknown chunk-attention backend {backend!r}")


def chunk_attention_paged(q, k_new, v_new, k_pool, k_scale, v_pool, v_scale,
                          pos_pool, table, positions, lengths, *, layer=0,
                          window: Optional[int] = None,
                          backend: str = DEFAULT_BACKEND,
                          tile: Optional[int] = None):
    """Chunk attention over a *paged* ring: identical semantics to
    ``chunk_attention`` on the virtual ring ``ref.gather_pages(pool,
    table)`` defines (mask rule unchanged, expressed in logical positions
    carried by ``pos_pool`` — prefill and decode L=1 stay unified).

    Extra operands vs the contiguous op: ``k_pool``/``v_pool`` are stacked
    physical pages (layers, P, page_size, KV·hd) (int8 with (layers, KV,
    P, page_size) scales, or float with scales None), ``pos_pool``
    (layers, P, page_size) the per-entry absolute positions, ``table``
    (B, n_pages) int32 physical page ids per logical page of layer
    ``layer``. Physical page 0 is the reserved null page (pos ≡ -1, never
    written): unmapped entries point at it and mask out.

    Backends mirror the contiguous op: ``materialized`` gathers the pages
    into a contiguous ring and runs ``chunk_attention_ref`` (the oracle by
    construction); ``stream``/``pallas`` walk logical tiles through the
    table without materializing the gather — with matching ``tile`` each
    is bit-identical to its contiguous-ring counterpart.
    """
    b, L, kv, g, hd = q.shape
    ps = k_pool.shape[2]
    n_pages = table.shape[1]
    cap = n_pages * ps
    backend = resolve_chunk_backend(backend)
    t = tile if tile is not None else paged_tile(ps, L)
    t = min(t, ps)
    while ps % t:  # tiles must divide the page — a spanning tile would need
        t -= 1     # a two-page gather
    interpret = jax.default_backend() != "tpu"
    if backend == "pallas" and not interpret:
        t = lane_tile(ps, t)
    _TRACK["peak_bytes"] = max(
        _TRACK["peak_bytes"],
        tracked_block_bytes(b, kv, g, L, cap, backend=backend, tile=t))
    if backend == "materialized":
        kp, ksp, vp, vsp, pp = _ref.ring_layer(k_pool, k_scale, v_pool,
                                               v_scale, pos_pool, layer, kv)
        gather = lambda a: None if a is None else gather_pages(a, table)
        return _ref.chunk_attention_ref(
            q, k_new, v_new, gather(kp), gather(ksp), gather(vp),
            gather(vsp), gather(pp), positions, lengths, window=window)
    if backend == "stream":
        return _stream_paged(q, k_new, v_new, k_pool, k_scale, v_pool,
                             v_scale, pos_pool, table, positions, lengths,
                             layer, window=window, tile=t)
    if backend == "pallas":
        out = chunk_attention_paged_pallas(
            q.transpose(0, 2, 3, 1, 4), k_new, v_new, k_pool, k_scale,
            v_pool, v_scale, pos_pool, table, positions,
            lengths.astype(jnp.int32), layer, window=window,
            tile=t, interpret=interpret)
        return out.transpose(0, 3, 1, 2, 4)
    raise ValueError(f"unknown chunk-attention backend {backend!r}")
