"""Flash chunk-prefill attention over the int8 ring cache.

One backend-dispatched op serves every attention read the serving engine
performs — bucketed chunk prefill, the fused decode loop (the L = 1 case),
and the serial admitter's decode — against (pre-write ring ∪ in-chunk keys)
with **online softmax**: the (L, cap + L) score block is never materialized,
and the int8 ring streams to the compute unit as int8, dequantized per tile
(halving attention weight traffic vs a full f32 dequant of the cache).

Op contract (stable; ``ops.chunk_attention``)
---------------------------------------------
::

  chunk_attention(q, k_new, v_new, k_cache, k_scale, v_cache, v_scale,
                  pos_buf, positions, lengths, *, layer=0, window=None,
                  backend="auto", tile=None)
      -> (B, L, KV, G, hd) float32

Inputs:
  q:          (B, L, KV, G, hd) rotary-applied queries, grouped per kv head
              (head h = kv * G + g, matching ``models.attention``).
  k_new/v_new:(B, L, KV, hd) the chunk's fresh keys/values (float — scored
              at full activation precision, *before* any cache write).
  k_cache/v_cache: (NL, B, cap, KV·hd) the stacked ring of NL layers
              **before** this chunk is written (storage below) — int8
              (with per-(slot, kv-head) absmax ``k_scale``/``v_scale``
              (NL, KV, B, cap) f32, kv-head-major) or float (scales =
              None).
  pos_buf:    (NL, B, cap) int32 absolute position held by each ring slot
              (-1 = empty).
  layer:      which of the NL layers is read (an int, or a traced int32
              scalar — the layer scan's index).
  positions:  (B, L) int32 absolute position of each chunk query.
  lengths:    (B,) int32 valid token count per row. Rows with length 0 are
              no-ops (their output is unconsumed garbage, finite by
              construction); key j of row r participates iff j < lengths[r].

Masking (the *exact* part of the contract — every backend must agree
bitwise on the visible set; floats may reorder):
  A query at absolute position p sees key at position s iff
  ``0 <= p - s < reach`` where ``reach = min(window or cap, cap)`` —
  i.e. causal, sliding-window-clipped, and never further back than the
  ring can faithfully hold. Ring entries additionally require
  ``pos_buf >= 0``; in-chunk keys additionally require validity
  (j < lengths[r]). This single rule reproduces the write-then-attend
  decode semantics at L = 1 (the entry at distance exactly ``cap`` is the
  one the token's own write evicts, so it is masked rather than read) and
  covers ring wrap and per-row chunk offsets with no special cases.

Ring storage (who allocates and writes it): ``models.attention.cache_init``
allocates each attention layer's ring in the layout above, and
``models.transformer`` stacks the layers of a scan on a leading axis and
carries the stack through the layer scan — the kernel reads it at the
scan's layer index, and the model writes each chunk's (or decode token's)
k/v/pos with one scatter at ``[layer, row, slot]`` after the read. Nothing
else copies, slices or relays out the ring. ``ref.ring_layer`` takes one
layer out in the oracle's logical layout ((B, cap, KV, hd), (B, cap, KV),
(B, cap)); ``ref.to_storage`` is its inverse.

Backends:
  * ``pallas``       — grid (batch, kv-head, tile); int8 tiles stream
                       HBM→VMEM and their scales apply to the scores, with
                       the online-softmax state carried across the tile
                       axis in VMEM (run by the Pallas interpreter
                       off-TPU, like ``ternary_matmul_pallas``).
  * ``stream``       — CPU/XLA fallback: a jitted ``fori_loop`` over
                       fixed-size ring tiles (sliced from the cache in
                       place) carrying running (max, sum, acc) state. Peak
                       attention allocation is O(L·tile) per layer instead
                       of O(L·(cap+L)); the scan dequantizes one int8 tile
                       at a time.
  * ``materialized`` — the pre-PR-5 path (full score block + full-ring
                       dequant, one softmax), kept as the measured baseline
                       and the parity oracle (``ref.chunk_attention_ref``).
  * ``auto``         — ``pallas`` on TPU, ``stream`` elsewhere.

``ops.tracked_block_bytes`` gives the analytic peak score-block bytes per
(shape, backend) — what the long-context benchmark and the O(L·tile) test
assert; ``ops.peak_tracked_bytes()`` records the same figure at trace time.

Paged variant (stable; ``ops.chunk_attention_paged``)
-----------------------------------------------------
::

  chunk_attention_paged(q, k_new, v_new, k_pool, k_scale, v_pool, v_scale,
                        pos_pool, table, positions, lengths, *, layer=0,
                        window=None, backend="auto", tile=None)
      -> (B, L, KV, G, hd) float32

The KV ring virtualized into fixed-size pages: ``k_pool``/``v_pool`` are
(NL, P, page_size, KV·hd) *physical* pages shared by the whole batch,
stacked over layers like the ring (int8 with (NL, KV, P, page_size)
scales, or float with scales None), ``pos_pool`` (NL, P, page_size) their
per-entry absolute positions, and ``table`` (B, n_pages) int32 maps
each row's logical page of layer ``layer`` to a physical one. The op
computes exactly ``chunk_attention`` over the virtual ring
``ring[b, p·ps + o] = pool[table[b, p], o]`` (``ref.gather_pages``) with
capacity ``n_pages · page_size`` — the same mask rule in logical
positions, so prefill, decode (L = 1), ring wrap, and sliding windows are
unchanged. Physical page 0 is the reserved **null page** (pos ≡ -1, never
written): unmapped table entries point at it and gather safely, masked by
the pos >= 0 rule — length-0 rows and partially mapped rings need no
special cases. Backends mirror the contiguous op; ``stream``/``pallas``
walk logical tiles through the table (tile divides page_size, one dynamic
page index per tile — pages are just non-contiguous tiles), and with
matching ``tile`` each backend is bit-identical to its contiguous-ring
counterpart (``materialized`` is gather-then-oracle, bit-identical by
construction). ``ops.paged_tile`` is the paged tile selector.
"""

from repro.kernels.chunk_attention.ops import (
    chunk_attention,
    chunk_attention_paged,
    paged_tile,
    peak_tracked_bytes,
    reset_tracking,
    resolve_chunk_backend,
    tracked_block_bytes,
)
from repro.kernels.chunk_attention.ref import gather_pages

__all__ = [
    "chunk_attention", "chunk_attention_paged", "gather_pages", "paged_tile",
    "resolve_chunk_backend", "tracked_block_bytes",
    "peak_tracked_bytes", "reset_tracking",
]
