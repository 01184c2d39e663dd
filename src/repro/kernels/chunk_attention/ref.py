"""Pure-jnp oracle for chunk-prefill attention over the ring cache.

This is the *materialized* implementation: the full (L, cap + L) score
block and a full-ring f32 dequant, one softmax — exactly the pre-PR-5
serving path, restated against the package's mask contract. It doubles as
the ``backend="materialized"`` baseline (it is jit-friendly) and as the
parity oracle for the Pallas kernel and the streaming fallback.

The oracle reads one layer of the ring in its *logical* layout — keys
(B, cap, KV, hd), scales (B, cap, KV), positions (B, cap) — which
``ring_layer`` takes out of the stacked storage the serving path keeps
(package docstring, "Ring storage"); ``to_storage`` is its inverse, for
tests that build a ring by hand.

The mask helpers here are the single source of truth for the visible set;
``ops`` and ``kernel`` reimplement them tile-wise and the tests assert the
reimplementations agree bitwise.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def reach_of(cap: int, window: Optional[int]) -> int:
    """Maximum causal distance a query may look back.

    ``min(window or cap, cap)``: sliding-window layers clip at the window,
    and nothing sees further back than the ring can faithfully hold — the
    entry at distance exactly ``cap`` is the one the query's own write
    evicts (write-then-attend decode semantics, generalized to chunks).
    """
    return min(window, cap) if window else cap


def history_mask(pos_buf, positions, reach: int):
    """(B, L, cap) bool: chunk query l of row b sees ring slot s."""
    d = positions[:, :, None] - pos_buf[:, None, :]
    return (pos_buf[:, None, :] >= 0) & (d >= 0) & (d < reach)


def chunk_mask(positions, lengths, reach: int):
    """(B, L, L) bool: chunk query l sees in-chunk key j (causal + valid)."""
    L = positions.shape[1]
    valid = jnp.arange(L)[None, None, :] < lengths[:, None, None]
    d = positions[:, :, None] - positions[:, None, :]
    return valid & (d >= 0) & (d < reach)


def kv_logical(x, kv: int):
    """(rows, slots, KV·hd) lane-dense keys or values → (rows, slots, KV,
    hd)."""
    r, n, f = x.shape
    return x.reshape(r, n, kv, f // kv)


def scale_logical(s):
    """(KV, rows, slots) int8 scales → (rows, slots, KV)."""
    return s.transpose(1, 2, 0)


def ring_layer(k_cache, k_scale, v_cache, v_scale, pos_buf, layer, kv: int):
    """Layer ``layer`` of a stacked ring (or paged pool) in the oracle's
    logical layout: (k, k_scale, v, v_scale, pos), scales None for a float
    ring."""
    sc = lambda a: None if a is None else scale_logical(a[layer])
    return (kv_logical(k_cache[layer], kv), sc(k_scale),
            kv_logical(v_cache[layer], kv), sc(v_scale), pos_buf[layer])


def to_storage(k, k_scale, v, v_scale, pos):
    """Inverse of ``ring_layer`` over every layer: logical leaves with a
    leading layer axis — k/v (NL, rows, slots, KV, hd), scales (NL, rows,
    slots, KV) or None, pos (NL, rows, slots) — in the stacked storage
    layout the op reads."""
    kvs = lambda a: a.reshape(a.shape[:3] + (-1,))
    sc = lambda a: None if a is None else a.transpose(0, 3, 1, 2)
    return kvs(k), sc(k_scale), kvs(v), sc(v_scale), pos


def _deq(c, scale):
    c = c.astype(jnp.float32)
    return c if scale is None else c * scale[..., None].astype(jnp.float32)


def gather_pages(pool, table):
    """Materialize the logical (B, n_pages·page_size, ...) ring of a paged
    cache: ``ring[b, p*ps + o] = pool[table[b, p], o]``.

    ``pool`` is (P, ps, ...) physical pages; ``table`` (B, n_pages) int32
    physical page ids. Physical page 0 is the reserved *null page* (pos ≡
    -1, never written), so unmapped table entries gather safely and the
    mask rule hides them — no special cases anywhere downstream. This
    helper defines paged semantics: every paged backend must equal
    ``chunk_attention_ref`` over this gather.
    """
    b, n = table.shape
    flat = pool[table.reshape(-1)]                           # (B·n, ps, ...)
    return flat.reshape((b, n * pool.shape[1]) + pool.shape[2:])


def chunk_attention_ref(q, k_new, v_new, k_cache, k_scale, v_cache, v_scale,
                        pos_buf, positions, lengths, *,
                        window: Optional[int] = None):
    """Materialized chunk attention; see the package docstring for shapes.

    Returns (B, L, KV, G, hd) float32.
    """
    b, L, kv, g, hd = q.shape
    cap = k_cache.shape[1]
    reach = reach_of(cap, window)
    scale = hd ** -0.5

    qf = q.astype(jnp.float32) * scale
    kc = _deq(k_cache, k_scale)                              # (B, cap, KV, hd)
    vc = _deq(v_cache, v_scale)
    s_hist = jnp.einsum("blkgd,bskd->bkgls", qf, kc)         # (B,KV,G,L,cap)
    m_hist = history_mask(pos_buf, positions, reach)         # (B, L, cap)
    s_hist = jnp.where(m_hist[:, None, None], s_hist, NEG_INF)

    knf = k_new.astype(jnp.float32)
    s_self = jnp.einsum("blkgd,bjkd->bkglj", qf, knf)        # (B,KV,G,L,L)
    m_self = chunk_mask(positions, lengths, reach)           # (B, L, L)
    s_self = jnp.where(m_self[:, None, None], s_self, NEG_INF)

    p = jax.nn.softmax(jnp.concatenate([s_hist, s_self], axis=-1), axis=-1)
    v_all = jnp.concatenate([vc, v_new.astype(jnp.float32)], axis=1)
    out = jnp.einsum("bkgls,bskd->blkgd", p, v_all)          # (B,L,KV,G,hd)
    return out
