"""Plain reference of a dense GQA decoder (model_type ``qwen2``).

Straight ``jax.numpy`` in float32 at HIGHEST matmul precision, with no
kernel, cache or batching trick: the whole sequence at once, causal
softmax attention, one layer at a time. It imports nothing of the program
under test; its weights come from the seed through the harness
(``weights.Seeded``: the seed under the architecture's leaf rules),
dequantized (Ŵ = α¹T¹ + α²T², exact in f32).

Layer equations (Qwen2, arXiv:2407.10671):

    h  = RMSNorm(x) · g_attn
    q, k, v = h W_q + b_q, h W_k + b_k, h W_v + b_v      (GQA, RoPE on q, k)
    x += softmax(q kᵀ / sqrt(hd) + causal) v · W_o
    h  = RMSNorm(x) · g_mlp
    x += (silu(h W_g) ⊙ h W_i) W_o2
    logits = RMSNorm(x) · g_final · W_head

Departure from the published model, as served: the output head is its own
(quantized) matrix, not tied to the embedding.

``fp8=True`` is the control: every matmul's operands are cast to
float8_e4m3fn with one absmax scale per tensor, accumulated in f32.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512


def _q8(a):
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32), s


def mm(x, w, fp8: bool):
    if fp8:
        (x8, sx), (w8, sw) = _q8(x), _q8(w)
        return jnp.einsum("...d,df->...f", x8, w8, precision=HI) * (sx * sw)
    return jnp.einsum("...d,df->...f", x, w, precision=HI)


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x (B, T, H, hd); rotate-half RoPE at absolute positions pos (T,)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("dims", "fp8"))
def _layer(x, p, *, dims, fp8):
    heads, kv, hd, eps, theta = dims
    b, t, _ = x.shape
    pos = jnp.arange(t)
    h = rms(x, p["attn_norm"], eps)
    q = (mm(h, p["wq"], fp8) + p["bq"]).reshape(b, t, heads, hd)
    k = (mm(h, p["wk"], fp8) + p["bk"]).reshape(b, t, kv, hd)
    v = (mm(h, p["wv"], fp8) + p["bv"]).reshape(b, t, kv, hd)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    g = heads // kv
    k = jnp.repeat(k, g, axis=2)          # query head h reads kv head h // g
    v = jnp.repeat(v, g, axis=2)
    blk = min(t, QUERY_BLOCK)             # queries per block, to bound memory

    def attend(args):
        qi, i = args                      # (b, blk, H, hd), block index
        s = jnp.einsum("bqhd,bshd->bhqs", qi, k, precision=HI) * hd ** -0.5
        causal = pos[None, :] <= (i * blk + jnp.arange(blk))[:, None]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        return jnp.einsum("bhqs,bshd->bqhd", jax.nn.softmax(s, -1), v,
                          precision=HI)

    qb = jnp.moveaxis(q.reshape(b, t // blk, blk, heads, hd), 1, 0)
    a = jax.lax.map(attend, (qb, jnp.arange(t // blk)))
    a = jnp.moveaxis(a, 0, 1).reshape(b, t, heads * hd)
    x = x + mm(a, p["wo"], fp8)
    h = rms(x, p["mlp_norm"], eps)
    y = jax.nn.silu(mm(h, p["wg"], fp8)) * mm(h, p["wi"], fp8)
    return x + mm(y, p["wo2"], fp8)


def _dims(c: Dict):
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    return (heads, kv, c["hidden_size"] // heads, float(c["rms_norm_eps"]),
            float(c["rope_theta"]))


def _layer_params(c: Dict, w, layer: int):
    d, ff = c["hidden_size"], c["intermediate_size"]
    heads, kv, hd, _, _ = _dims(c)
    grp = c["quantization"]["group_size"]
    dt = c["torch_dtype"]
    W = lambda path, i, o: w.matrix(f"/blocks/b0/{path}/kernel", layer, i,
                                    o, grp)
    V = lambda path, n: w.leaf(f"/blocks/b0/{path}", layer, (n,), dt)
    return {
        "attn_norm": V("attn_norm/scale", d), "mlp_norm": V("mlp_norm/scale", d),
        "wq": W("attn/wq", d, heads * hd), "bq": V("attn/wq/bias", heads * hd),
        "wk": W("attn/wk", d, kv * hd), "bk": V("attn/wk/bias", kv * hd),
        "wv": W("attn/wv", d, kv * hd), "bv": V("attn/wv/bias", kv * hd),
        "wo": W("attn/wo", heads * hd, d),
        "wg": W("mlp/wg", d, ff), "wi": W("mlp/wi", d, ff),
        "wo2": W("mlp/wo", ff, d),
    }


@jax.jit
def _embed(table, tokens):
    return jnp.take(table, tokens, axis=0)


def final_hidden(c: Dict, w, tokens, fp8: bool = False):
    """(B, T) token ids -> (B, T, d) final-normed hidden states, f32, with
    the weights that ``w`` (``weights.Seeded``) draws."""
    d, v = c["hidden_size"], c["vocab_size"]
    table = w.leaf("/embed/embedding", -1, (v, d), c["torch_dtype"])
    x = _embed(table, jnp.asarray(tokens))
    del table
    for layer in range(c["num_hidden_layers"]):
        x = _layer(x, _layer_params(c, w, layer), dims=_dims(c), fp8=fp8)
    scale = w.leaf("/final_norm/scale", -1, (d,), c["torch_dtype"])
    return rms(x, scale, float(c["rms_norm_eps"]))


def head(c: Dict, w):
    """The output head Ŵ (d, V), f32."""
    return w.matrix("/lm_head/kernel", -1, c["hidden_size"], c["vocab_size"],
                    c["quantization"]["group_size"])
