"""GQA attention: chunked full-causal and sliding-window variants + ring cache.

Memory-safe by construction: the (S × S) score matrix is never materialized —
training/prefill scans over query chunks (full attention: each chunk scores
against all keys; local attention: only against the ⌈W/C⌉+1 covering key
chunks, giving the O(S·W) FLOP count that the gemma3/recurrentgemma roofline
requires).

The decode cache is a *ring buffer* with per-slot absolute positions:
full-attention layers use capacity = max context, sliding-window layers use
capacity = W (so a gemma3 local layer at 500k context holds 1024 slots, not
500k — the cache-memory optimization that makes `long_500k` feasible).
One implementation serves both (window = capacity ⇒ full attention).

The ring is stored as the attention kernel reads it — k/v (B, cap, KV·hd)
lane-dense, int8 scales (KV, B, cap), positions (B, cap) — and the
layers of a scan are one stack with a leading layer axis that the layer
scan carries in place (``models.transformer``). Every function here takes
that stack plus the layer index (or one unscanned layer's cache with
``layer=None``): the kernel reads the layer in place, and the only write
is one scatter of the chunk's (or decode token's) k/v/pos at
``[layer, row, slot]``, after the read. The paged pool (``pages_*``) is
stored and written the same way, through the page table.

Every serving-time attention read — chunk prefill and single-token decode
(its L = 1 case) — goes through one backend-dispatched op,
``repro.kernels.chunk_attention``: online softmax against (pre-write ring ∪
in-chunk keys), the int8 ring dequantized tile-by-tile at the compute unit,
never as a whole. ``cfg.attn_backend`` selects the implementation (Pallas
on TPU, the streaming tile-loop fallback elsewhere, or the materialized
baseline); the visible-set rule is identical across backends.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.chunk_attention.ops import (chunk_attention,
                                               chunk_attention_paged)
from repro.models.common import apply_rope, dense, dense_init, norm_init, rms_norm

NEG_INF = -1e30


def attn_init(key, cfg, dtype) -> Dict[str, Any]:
    hd = cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], cfg.d_model, cfg.n_heads * hd, bias=cfg.qkv_bias,
                         dtype=dtype),
        "wk": dense_init(ks[1], cfg.d_model, cfg.n_kv_heads * hd,
                         bias=cfg.qkv_bias, dtype=dtype),
        "wv": dense_init(ks[2], cfg.d_model, cfg.n_kv_heads * hd,
                         bias=cfg.qkv_bias, dtype=dtype),
        "wo": dense_init(ks[3], cfg.n_heads * hd, cfg.d_model, dtype=dtype),
    }


def _qkv(params, cfg, x, positions):
    b, s, _ = x.shape
    hd = cfg.head_dim
    q = dense(params["wq"], x).reshape(b, s, cfg.n_heads, hd)
    k = dense(params["wk"], x).reshape(b, s, cfg.n_kv_heads, hd)
    v = dense(params["wv"], x).reshape(b, s, cfg.n_kv_heads, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _gqa_scores(q, k):
    """q: (B, Sq, KV, G, hd), k: (B, Sk, KV, hd) -> (B, KV, G, Sq, Sk) f32."""
    return jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                      preferred_element_type=jnp.float32)


def _gqa_out(p, v):
    """p: (B, KV, G, Sq, Sk), v: (B, Sk, KV, hd) -> (B, Sq, KV, G, hd)."""
    return jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(p.dtype))


def _pick_chunk(s: int, target: int) -> int:
    for c in range(min(target, s), 0, -1):
        if s % c == 0:
            return c
    return s


def attention_forward(
    params: Dict[str, Any],
    cfg,
    x: jax.Array,
    positions: jax.Array,
    *,
    window: Optional[int] = None,
    q_chunk: int = 1024,
    return_kv: bool = False,
):
    """Full-sequence causal (optionally sliding-window) attention.

    Args:
      x: (B, S, D); positions: (S,) absolute positions (training: arange).
      window: sliding-window size; None = full causal.
      return_kv: also return the rotary-applied (k, v) for cache prefill.
    """
    b, s, _ = x.shape
    hd = cfg.head_dim
    kv, g = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    scale = hd ** -0.5

    q, k, v = _qkv(params, cfg, x, positions[None, :])
    q = q.reshape(b, s, kv, g, hd)

    c = _pick_chunk(s, q_chunk)
    n_chunks = s // c

    if window is None or window >= s:
        # full causal: each q chunk scores against all keys
        def body(_, i):
            q_i = jax.lax.dynamic_slice(q, (0, i * c, 0, 0, 0),
                                        (b, c, kv, g, hd))
            qpos = jax.lax.dynamic_slice(positions, (i * c,), (c,))
            logits = _gqa_scores(q_i, k) * scale  # (B,KV,G,c,S)
            mask = positions[None, :] <= qpos[:, None]  # (c, S)
            logits = jnp.where(mask[None, None, None], logits, NEG_INF)
            p = jax.nn.softmax(logits, axis=-1)
            return None, _gqa_out(p, v)

        _, outs = jax.lax.scan(body, None, jnp.arange(n_chunks))
    else:
        w = window
        n_prev = -(-w // c)  # chunks of history needed left of the q chunk
        span = (n_prev + 1) * c

        def body(_, i):
            q_i = jax.lax.dynamic_slice(q, (0, i * c, 0, 0, 0),
                                        (b, c, kv, g, hd))
            start = jnp.maximum(i * c - n_prev * c, 0)
            k_i = jax.lax.dynamic_slice(k, (0, start, 0, 0), (b, min(span, s), kv, hd))
            v_i = jax.lax.dynamic_slice(v, (0, start, 0, 0), (b, min(span, s), kv, hd))
            qpos = jax.lax.dynamic_slice(positions, (i * c,), (c,))
            kpos = jax.lax.dynamic_slice(positions, (start,), (min(span, s),))
            logits = _gqa_scores(q_i, k_i) * scale
            mask = (kpos[None, :] <= qpos[:, None]) & (
                qpos[:, None] - kpos[None, :] < w
            )
            logits = jnp.where(mask[None, None, None], logits, NEG_INF)
            p = jax.nn.softmax(logits, axis=-1)
            return None, _gqa_out(p, v_i)

        _, outs = jax.lax.scan(body, None, jnp.arange(n_chunks))

    # outs: (n_chunks, B, c, KV, G, hd) -> (B, S, H*hd)
    y = jnp.moveaxis(outs, 0, 1).reshape(b, s, cfg.n_heads * hd)
    y = dense(params["wo"], y.astype(x.dtype))
    if return_kv:
        return y, (k, v)
    return y


# ---------------------------------------------------------------------------
# decode: ring-buffer KV cache
# ---------------------------------------------------------------------------

# leaf names of the ring and of the paged pool: k, v, k scale, v scale, pos
_RING = ("k", "v", "k_scale", "v_scale", "pos")
_PAGES = ("pages_k", "pages_v", "pages_ks", "pages_vs", "pages_pos")


def cache_init(cfg, batch: int, capacity: int, window: Optional[int],
               dtype, *, kv_spec: Optional[Dict[str, int]] = None
               ) -> Dict[str, Any]:
    """Ring cache of one layer. capacity = min(window, max_context) for
    local layers.

    Stored as ``repro.kernels.chunk_attention`` reads it (its "Ring
    storage"), so no call relays it out: ``k``/``v`` (B, cap, KV·hd)
    lane-dense, ``pos`` (B, cap) (-1 = empty slot). The layers of a
    scan are stacked on a leading axis by ``models.transformer``.

    kv_cache_dtype="int8" (§Perf it. 5, beyond-paper): k/v stored int8 with
    per-(slot, kv-head) absmax scales ``k_scale``/``v_scale`` (KV, B, cap),
    kv-head-major (``row_axis``) — halves cache HBM capacity AND the
    decode-read traffic that dominates the decode_32k memory term.

    ``kv_spec = {"page_size": ps, "max_pages": n}`` selects the *paged*
    layout instead (see :func:`paged_cache_init`).
    """
    if kv_spec is not None:
        return paged_cache_init(cfg, batch, capacity, window, dtype,
                                page_size=kv_spec["page_size"],
                                max_pages=kv_spec["max_pages"])
    cap = min(window, capacity) if window else capacity
    return _kv_storage(cfg, batch, cap, dtype, _RING)


def _kv_storage(cfg, rows: int, slots: int, dtype, names) -> Dict[str, Any]:
    """Zeroed k/v (rows, slots, KV·hd), int8 scales (KV, rows, slots) and
    positions (rows, slots) = -1 under ``names`` (``_RING`` or
    ``_PAGES``)."""
    kn, vn, ksn, vsn, pn = names
    f = cfg.n_kv_heads * cfg.head_dim
    out = {pn: jnp.full((rows, slots), -1, jnp.int32)}
    if cfg.kv_cache_dtype == "int8":
        dtype = jnp.int8
        for n in (ksn, vsn):
            out[n] = jnp.zeros((cfg.n_kv_heads, rows, slots), jnp.float32)
    out[kn] = jnp.zeros((rows, slots, f), dtype)
    out[vn] = jnp.zeros((rows, slots, f), dtype)
    return out


def row_axis(name: str) -> int:
    """The axis of one layer's cache leaf ``name`` that holds its rows
    (batch rows, or physical pages for ``pages_*``). int8 scales are
    kv-head-major, (KV, rows, slots): their rows and slots then keep the
    TPU's default tiling, which the kernel reads and the scatter writes
    alike; every other leaf leads with its rows. A scan's stacked leaves
    add one leading layer axis."""
    return 1 if name in ("k_scale", "v_scale", "pages_ks", "pages_vs") else 0


def paged_cache_init(cfg, batch: int, capacity: int, window: Optional[int],
                     dtype, *, page_size: int, max_pages: int
                     ) -> Dict[str, Any]:
    """Paged KV cache: one batch-global pool of fixed-size pages plus a
    per-row page table (``repro.kernels.chunk_attention`` paged contract).

    Pool leaves are named ``pages_*`` and stored like the ring with pages
    for rows: ``pages_k``/``pages_v`` (P, page_size, KV·hd), ``pages_ks``/
    ``pages_vs`` (KV, P, page_size), ``pages_pos`` (P, page_size).
    They are *physical* storage owned by the allocator, not per-row state,
    and the engine's row reset skips them. ``table`` (B, n_pages) int32 is
    per-row; entry 0 points at the reserved null page (``pages_pos[0] ≡
    -1``, never written), so an unmapped logical page reads as empty. The
    pool holds ``max_pages`` allocatable pages + the null page, rounded up
    to a multiple of 8 pages (the kernel reads page positions 8 pages at a
    time; the spare pages are never mapped).

    Sliding-window layers are rejected: paging virtualizes one uniform
    logical capacity per row, and a window < capacity layer would need its
    own shorter ring (use ``kv_layout="ring"`` for such models).
    """
    if window is not None and window < capacity:
        raise ValueError(
            f"paged KV layout requires full-capacity attention layers "
            f"(window {window} < capacity {capacity}); use the ring layout "
            "for sliding-window models")
    if capacity % page_size:
        raise ValueError(f"page_size {page_size} must divide "
                         f"capacity {capacity}")
    n_phys = -(-(max_pages + 1) // 8) * 8                    # + null page
    cache = _kv_storage(cfg, n_phys, page_size, dtype, _PAGES)
    cache["table"] = jnp.zeros((batch, capacity // page_size), jnp.int32)
    return cache


def _q8(x):
    """absmax int8 quantization over the trailing (head) dim."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-10)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _as_stack(cache, layer):
    """(stacked cache, layer index, inverse). A scan's cache is already
    the stack and ``layer`` its index; an unscanned layer's cache (layer
    None) gains a leading axis of one, which costs no copy."""
    if layer is not None:
        return cache, layer, lambda c: c
    return (jax.tree.map(lambda a: a[None], cache), 0,
            lambda c: jax.tree.map(lambda a: a[0], c))


def _capacity(cache) -> int:
    if "table" in cache:
        return cache["table"].shape[-1] * cache["pages_k"].shape[2]
    return cache["k"].shape[2]


def _attend(cfg, cache, layer, q, k, v, positions, lengths, window):
    """Chunk attention of q (B, L, KV, G, hd) against layer ``layer`` of
    the stacked ring or pool (read in place) ∪ the chunk's own k/v."""
    lengths = lengths.astype(jnp.int32)
    if "table" in cache:
        return chunk_attention_paged(
            q, k, v, cache["pages_k"], cache.get("pages_ks"),
            cache["pages_v"], cache.get("pages_vs"), cache["pages_pos"],
            cache["table"][layer], positions, lengths, layer=layer,
            window=window, backend=cfg.attn_backend)
    return chunk_attention(
        q, k, v, cache["k"], cache.get("k_scale"), cache["v"],
        cache.get("v_scale"), cache["pos"], positions, lengths, layer=layer,
        window=window, backend=cfg.attn_backend)


def _after(y, cache):
    """Order the ring's write after the kernel's read of it: the write then
    updates the stack in place, where XLA would otherwise copy the stack
    to keep the read's operand intact."""
    return jax.lax.optimization_barrier((y, cache))


def _store(cache, layer, slots, k, v, positions):
    """Write chunk k/v (B, S, KV, hd) and their positions (B, S) at
    logical ring ``slots`` (B, S) of layer ``layer`` of the stacked
    storage: one scatter per leaf at ``[layer, row, slot]``, so only the
    slots tokens land in are written. Slot == cap means "drop this entry"
    (padding, and wrap-around entries the row's own chunk tail overwrites).

    Paged storage resolves each logical slot through the layer's page
    table to (physical page, offset). Writes that resolve to the null page
    (table entry 0 — an unmapped logical page) are dropped too: the null
    page's pos ≡ -1 invariant is what makes unmapped gathers safe, so
    nothing may ever dirty it. Distinct rows never map a writable logical
    page to the same physical page (the allocator copy-on-write-forks
    shared pages before any dispatch that writes them), so the scatter has
    no cross-row collisions.
    """
    b, s = slots.shape
    if "table" in cache:
        names = _PAGES
        n_phys, ps = cache["pages_k"].shape[1:3]
        n_pages = cache["table"].shape[-1]
        page = jnp.clip(slots // ps, 0, n_pages - 1)
        phys = jnp.take_along_axis(cache["table"][layer], page, axis=1)
        drop = (slots >= n_pages * ps) | (phys == 0)
        rows = jnp.where(drop, n_phys, phys)                 # out of range
        slots = slots % ps
    else:
        names = _RING
        rows = jnp.broadcast_to(jnp.arange(b)[:, None], (b, s))
    kn, vn, ksn, vsn, pn = names
    out = dict(cache)
    out[pn] = cache[pn].at[layer, rows, slots].set(
        positions.astype(jnp.int32), mode="drop")
    if ksn in cache:  # int8 storage: quantize the written entries
        (k, ks), (v, vs) = _q8(k), _q8(v)
        # every (head, row, slot) indexed: an update window over the head
        # axis makes XLA lay the stack out head-minor for the scatter, and
        # relay it out for the kernel on every call
        heads = jnp.arange(ks.shape[-1])
        for n, sc in ((ksn, ks), (vsn, vs)):
            out[n] = cache[n].at[layer, heads, rows[..., None],
                                 slots[..., None]].set(sc, mode="drop")
    for n, val in ((kn, k), (vn, v)):
        out[n] = cache[n].at[layer, rows, slots].set(
            val.reshape(b, s, -1).astype(cache[n].dtype), mode="drop")
    return out


def cache_prefill(cfg, cache: Dict[str, Any], k, v, positions, *,
                  layer=None) -> Dict[str, Any]:
    """Write a full prefill sequence into the ring (keeps the last `cap`).

    k/v: (B, S, KV, hd); positions: (B, S) absolute. ``layer``: this
    layer's index in a scan's stacked cache, None for an unscanned layer.
    """
    cache, layer, unstack = _as_stack(cache, layer)
    cap = _capacity(cache)
    if k.shape[1] > cap:
        k, v, positions = k[:, -cap:], v[:, -cap:], positions[:, -cap:]
    slots = (positions % cap).astype(jnp.int32)
    return unstack(_store(cache, layer, slots, k, v, positions))


def attention_prefill_chunk(
    params: Dict[str, Any],
    cfg,
    cache: Dict[str, Any],
    x: jax.Array,
    positions: jax.Array,
    lengths: jax.Array,
    *,
    window: Optional[int] = None,
    layer=None,
) -> Tuple[jax.Array, Dict[str, Any]]:
    """Padded-batch chunk prefill: attend to (ring cache ∪ chunk), then write.

    Args:
      x: (B, L, D) right-padded chunk; positions: (B, L) absolute positions
      (row r valid through positions[r, lengths[r]-1]); lengths: (B,) valid
      token counts — 0 makes the row a complete no-op (its cache survives
      untouched, so decoding/free rows can ride along in a fixed-shape
      dispatch); layer: this layer's index in a scan's stacked cache, None
      for an unscanned layer.

    The chunk queries score against the *pre-write* ring (history from
    earlier chunks — for sliding-window layers the ring holds exactly the
    last `cap` positions, which covers every in-chunk query's window) and
    against the in-chunk keys, in one online softmax via
    ``repro.kernels.chunk_attention`` — the (L, cap+L) score block is never
    materialized and the int8 ring is dequantized per streamed tile, not as
    a whole (``cfg.attn_backend`` picks the implementation). Afterwards the
    chunk k/v are scattered into the ring; padding and entries a row's own
    chunk tail would immediately overwrite (length > cap) are dropped.
    """
    cache, layer, unstack = _as_stack(cache, layer)
    b, L, _ = x.shape
    hd = cfg.head_dim
    kv, g = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    cap = _capacity(cache)

    q, k, v = _qkv(params, cfg, x, positions)
    y, cache = _after(_attend(cfg, cache, layer, q.reshape(b, L, kv, g, hd),
                              k, v, positions, lengths, window), cache)
    y = y.reshape(b, L, cfg.n_heads * hd).astype(x.dtype)
    y = dense(params["wo"], y)

    # write the chunk into the ring (drop padding + beyond-ring tail)
    valid = jnp.arange(L)[None, :] < lengths[:, None]        # (B, L)
    row_end = positions[:, :1] + lengths[:, None]            # (B, 1)
    keep = valid & (positions >= row_end - cap)
    slots = jnp.where(keep, positions % cap, cap).astype(jnp.int32)
    return y, unstack(_store(cache, layer, slots, k, v, positions))


def attention_decode(
    params: Dict[str, Any],
    cfg,
    cache: Dict[str, Any],
    x_t: jax.Array,
    pos: jax.Array,
    *,
    window: Optional[int] = None,
    active: Optional[jax.Array] = None,
    layer=None,
) -> Tuple[jax.Array, Dict[str, Any]]:
    """One-token decode. x_t: (B, D); pos: (B,) absolute position of x_t;
    layer: this layer's index in a scan's stacked cache, None for an
    unscanned layer.

    active (B,) bool: rows with active=False leave the ring untouched (their
    write is dropped) — required when decode shares the batch state with
    rows that are still mid-prefill (their caches must not be corrupted).

    Routed through ``repro.kernels.chunk_attention`` as the L = 1 case:
    the token scores against (pre-write ring ∪ itself) under the shared
    mask rule — the op's ``reach`` cap masks the slot this token's own
    write evicts, which is exactly the write-then-attend semantics — so
    decode, chunked prefill, and the serial path share one masking
    implementation. active=False rows pass length 0 (no self key, no
    write), mirroring their dropped write.
    """
    cache, layer, unstack = _as_stack(cache, layer)
    b, _ = x_t.shape
    hd = cfg.head_dim
    kv, g = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    cap = _capacity(cache)

    q = dense(params["wq"], x_t).reshape(b, cfg.n_heads, hd)
    k_t = dense(params["wk"], x_t).reshape(b, kv, hd)
    v_t = dense(params["wv"], x_t).reshape(b, 1, kv, hd)
    q = apply_rope(q, pos, cfg.rope_theta)
    k_t = apply_rope(k_t, pos, cfg.rope_theta)[:, None]

    lengths = (active.astype(jnp.int32) if active is not None
               else jnp.ones((b,), jnp.int32))
    positions = pos[:, None].astype(jnp.int32)
    y, cache = _after(_attend(cfg, cache, layer, q.reshape(b, 1, kv, g, hd),
                              k_t, v_t, positions, lengths, window), cache)
    y = y.reshape(b, cfg.n_heads * hd).astype(x_t.dtype)
    y = dense(params["wo"], y)

    slot = (pos % cap).astype(jnp.int32)  # (B,)
    if active is not None:
        slot = jnp.where(active, slot, cap)  # cap = out of ring → dropped
    return y, unstack(_store(cache, layer, slot[:, None], k_t, v_t,
                             positions))
