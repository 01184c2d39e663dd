"""Multi-head latent attention (MLA, DeepSeek-V2, arXiv:2405.04434).

Per token and layer the cache holds one vector of ``kv_lora_rank +
qk_rope_head_dim`` lanes: the normed latent c = RMSNorm(x W_DKV) and the
roped key part k_pe = RoPE(x W_KR), one head shared by every query head.
The published layer (no query LoRA):

    q = x W_Q → per head (q_nope ‖ q_pe), q_pe roped
    c ‖ k_pe = x W_DKV,KR;   c = RMSNorm(c);   k_pe roped
    k_nope ‖ v = c W_UKV → per head
    o_h = softmax(s · (q_nope·k_noped + q_pe·k_pe)) v_h;   y = o W_O

with YaRN rope and s = (nope + rope)^-½ · mscale_all². ``mla_forward``
(training, evaluation) computes it so. Serving absorbs W_UK into the
query and W_UV into the output, so attention runs in the latent space:
q_lat = q_nope · W_UKᵀ (kv_lora_rank lanes per head), scores over
(q_lat ‖ q_pe) · (c ‖ k_pe), the softmax weights times c, then · W_UV per
head — ``repro.kernels.chunk_attention.latent_attention``. W_UK and W_UV
are dequantized from ``wkv_b``'s trit-planes inside the step, in the
activation dtype (as the ternary kernel rounds Ŵ), so the planes stay the
only copy of the weights. Chunk prefill and decode (its L = 1 case) are
one path, ``mla_chunk``; the ring is the stacked, in-place one of
``models.attention`` with a ``latent`` leaf for ``k``/``v``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.packing import unpack_trits
from repro.core.quantize_model import QuantizedKernel
from repro.kernels.chunk_attention import latent_attention
from repro.kernels.ternary_matmul.ref import dequantize
from repro.models.attention import NEG_INF, _after, _as_stack, _pick_chunk
from repro.models.common import (apply_rope, dense, dense_init, norm_init,
                                 rms_norm, yarn_mscale)


def mla_init(key, cfg, dtype) -> Dict[str, Any]:
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope, v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d, h * (nope + rope), dtype=dtype),
        "wkv_a": dense_init(ks[1], d, r + rope, dtype=dtype),
        "kv_norm": norm_init(r, dtype),
        "wkv_b": dense_init(ks[2], r, h * (nope + v), dtype=dtype),
        "wo": dense_init(ks[3], h * v, d, dtype=dtype),
    }


def softmax_scale(cfg) -> float:
    s = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    y = cfg.rope_yarn
    if y is not None and y.mscale_all_dim:
        m = yarn_mscale(y.factor, y.mscale_all_dim)
        s *= m * m
    return s


def _rope(cfg, x, positions):
    return apply_rope(x, positions, cfg.rope_theta, yarn=cfg.rope_yarn,
                      interleave=cfg.rope_interleave)


def _project(params, cfg, x, positions):
    """(q_nope (B, S, H, nope), roped q_pe (B, S, H, rope), latent (B, S,
    r + rope) = normed c ‖ roped k_pe)."""
    b, s, _ = x.shape
    h, r, nope = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    q = dense(params["wq"], x).reshape(b, s, h, -1)
    q_pe = _rope(cfg, q[..., nope:], positions)
    kv = dense(params["wkv_a"], x)
    c = rms_norm(params["kv_norm"], kv[..., :r], cfg.norm_eps)
    k_pe = _rope(cfg, kv[..., None, r:], positions)[..., 0, :]
    return q[..., :nope], q_pe, jnp.concatenate([c, k_pe], axis=-1)


def mla_forward(params, cfg, x, positions, *, q_chunk: int = 1024):
    """Full-sequence causal MLA, un-absorbed. x (B, S, D), positions (S,)."""
    b, s, _ = x.shape
    h, r, nope = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    q_nope, q_pe, lat = _project(params, cfg, x, positions)
    kv = dense(params["wkv_b"], lat[..., :r]).reshape(b, s, h, -1)
    k_pe = jnp.broadcast_to(lat[..., None, r:], q_pe.shape)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
    v = kv[..., nope:]
    scale = softmax_scale(cfg)
    c = _pick_chunk(s, q_chunk)

    def body(_, i):
        q_i = jax.lax.dynamic_slice_in_dim(q, i * c, c, axis=1)
        qpos = jax.lax.dynamic_slice_in_dim(positions, i * c, c)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q_i, k,
                            preferred_element_type=jnp.float32) * scale
        mask = positions[None, :] <= qpos[:, None]
        logits = jnp.where(mask[None, None], logits, NEG_INF)
        p = jax.nn.softmax(logits, axis=-1)
        return None, jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(p.dtype))

    _, outs = jax.lax.scan(body, None, jnp.arange(s // c))
    y = jnp.moveaxis(outs, 0, 1).reshape(b, s, h * cfg.v_head_dim)
    return dense(params["wo"], y.astype(x.dtype))


def _absorbed(params, cfg, dtype):
    """(W_UK (r, H, nope), W_UV (r, H, v)) of ``wkv_b`` in ``dtype``."""
    k = params["wkv_b"]["kernel"]
    if isinstance(k, QuantizedKernel):
        t1, t2 = k.t1p, k.t2p
        if t1.dtype == jnp.uint8:      # packed (else unpacked by the engine)
            t1, t2 = unpack_trits(t1), unpack_trits(t2)
        k = dequantize(t1, t2, k.alpha, k.group_size).T
    w = k.astype(dtype).reshape(cfg.kv_lora_rank, cfg.n_heads, -1)
    nope = cfg.qk_nope_head_dim
    return w[..., :nope], w[..., nope:]


def latent_width(cfg) -> int:
    """Lanes of a cached latent: c ‖ k_pe (r + rope) padded with zeros to
    a whole number of 128-lane tiles, which the TPU's tiled layout holds
    anyway; the scores over the zero lanes add nothing, and a ring whose
    minor dim is lane-aligned is scattered and read in the layout it is
    stored in (an unaligned one is relaid out around every write)."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // 128) * 128


def latent_cache_init(cfg, batch: int, capacity: int, dtype
                      ) -> Dict[str, Any]:
    """One layer's latent ring: ``latent`` (B, cap, ``latent_width``) in
    the activation dtype and ``pos`` (B, cap), -1 = empty slot."""
    if cfg.kv_cache_dtype == "int8":
        raise ValueError("the latent cache has no int8 layout")
    return {"latent": jnp.zeros((batch, capacity, latent_width(cfg)), dtype),
            "pos": jnp.full((batch, capacity), -1, jnp.int32)}


def mla_chunk(params, cfg, cache, x, positions, lengths, *, layer=None):
    """Absorbed MLA of a right-padded chunk against (latent ring ∪ chunk),
    then the chunk's latents written into the ring.

    x (B, L, D); positions (B, L); lengths (B,) valid tokens per row (0:
    a no-op row whose ring is left as it was); layer: this layer's index
    in a scan's stacked ring, None for an unscanned layer. Decode is
    L = 1 with lengths = active. Returns (y (B, L, D), cache).
    """
    cache, layer, unstack = _as_stack(cache, layer)
    b, L, _ = x.shape
    cap, cap_w = cache["latent"].shape[2:]
    with jax.named_scope("mla"):
        q_nope, q_pe, lat = _project(params, cfg, x, positions)
        pad = [(0, 0)] * (lat.ndim - 1) + [(0, cap_w - lat.shape[-1])]
        lat = jnp.pad(lat, pad).astype(cache["latent"].dtype)
        w_uk, w_uv = _absorbed(params, cfg, x.dtype)
        q_lat = jnp.einsum("blhn,rhn->blhr", q_nope, w_uk,
                           preferred_element_type=jnp.float32)
        q = jnp.concatenate([q_lat.astype(x.dtype), q_pe], axis=-1)
        q = jnp.pad(q, [(0, 0)] * 3 + pad[-1:])
        o, cache = _after(latent_attention(
            q, lat, cache["latent"], cache["pos"], positions, lengths,
            scale=softmax_scale(cfg), v_dim=cfg.kv_lora_rank, layer=layer,
            backend=cfg.attn_backend), cache)
        y = jnp.einsum("blhr,rhv->blhv", o.astype(x.dtype), w_uv,
                       preferred_element_type=jnp.float32)
        y = dense(params["wo"], y.reshape(b, L, -1).astype(x.dtype))

        valid = jnp.arange(L)[None, :] < lengths[:, None]
        row_end = positions[:, :1] + lengths[:, None]
        keep = valid & (positions >= row_end - cap)
        slots = jnp.where(keep, positions % cap, cap).astype(jnp.int32)
        rows = jnp.broadcast_to(jnp.arange(b)[:, None], (b, L))
        out = dict(cache)
        out["pos"] = cache["pos"].at[layer, rows, slots].set(
            positions.astype(jnp.int32), mode="drop")
        out["latent"] = cache["latent"].at[layer, rows, slots].set(
            lat, mode="drop")
    return y, unstack(out)


def mla_decode(params, cfg, cache, x_t, pos, *,
               active: Optional[jax.Array] = None, layer=None):
    """One-token decode: ``mla_chunk`` at L = 1 (rows with active=False
    neither attend with a self key nor write)."""
    lengths = (active.astype(jnp.int32) if active is not None
               else jnp.ones((x_t.shape[0],), jnp.int32))
    y, cache = mla_chunk(params, cfg, cache, x_t[:, None],
                         pos[:, None].astype(jnp.int32), lengths,
                         layer=layer)
    return y[:, 0], cache
