"""Plain reference of DeepSeek-V2 (model_type ``deepseek_v2``).

Straight ``jax.numpy`` in float32 at HIGHEST matmul precision, with no
kernel, cache, batching or absorption trick: the whole sequence at once,
one layer at a time. It imports nothing of the program under test; its
weights come from the seed through the harness (``weights.Seeded``),
dequantized (Ŵ = α¹T¹ + α²T², exact in f32).

Layer equations (DeepSeek-V2, arXiv:2405.04434; the published modelling
code's ``DeepseekV2Attention``, ``DeepseekV2MoE``):

    h = RMSNorm(x) · g_attn
    q = h W_q → per head (q_nope 128 ‖ q_pe 64)
    c ‖ k_pe = h W_kv_a;   c = RMSNorm(c) · g_kv          (one k_pe head)
    q_pe, k_pe = YaRN-RoPE(q_pe), YaRN-RoPE(k_pe)         (interleaved pairs)
    k_nope ‖ v = c W_kv_b → per head (128 ‖ 128)
    x += softmax(s · [q_nope ‖ q_pe] · [k_nope ‖ k_pe]ᵀ + causal) v · W_o
    h = RMSNorm(x) · g_mlp
    layer < first_k_dense_replace:  x += (silu(h W_g) ⊙ h W_i) W_o2
    else: p = softmax(h W_router);  the k largest p_e, not renormalized
          (norm_topk_prob false), times routed_scaling_factor (1), are
          the gates g_e;  x += Σ_e g_e · FFN_e(h) + FFN_shared(h)
    logits = RMSNorm(x) · g_final · W_head

YaRN as the modelling code computes it: inv_freq blended between θ^(−2i/d)
and θ^(−2i/d) / factor over a linear ramp of dims [low, high] from the
correction range of β_fast, β_slow at the original context; cos and sin
times mscale(factor, mscale) / mscale(factor, mscale_all_dim); s =
(nope + rope)^−½ · mscale(factor, mscale_all_dim)², mscale(f, m) =
0.1 · m · ln f + 1. Interleaved pairs: rope dims (2i, 2i + 1) are gathered
into halves before the rotate-half rotation, as the code's view/transpose
does.

Departures, as served:
- each routed expert is applied, dequantized one matrix at a time, to the
  tokens routed to it (gathered on the host, padded to a power of two),
  its output times the token's gate added back; applying it to every
  token with a zero gate elsewhere is the same sum at 64 / 6 times the
  matmuls;
- a matrix whose contraction the group does not divide (layer 0's down
  projection, d_in 10,944) is a bf16 leaf, as the program keeps it;
- the output head is its own (quantized) matrix, as published.

``fp8=True`` is the control: every matmul's operands are cast to
float8_e4m3fn with one absmax scale per tensor, accumulated in f32; the
control routes by its own router logits.
"""

from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

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


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(c: Dict):
    """The YaRN-blended inverse frequencies (rope_dim / 2,), float64."""
    dim, base = c["qk_rope_head_dim"], float(c["rope_theta"])
    rs = c["rope_scaling"]
    factor, orig = float(rs["factor"]), rs["original_max_position_embeddings"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = extra / factor
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    keep = 1.0 - ramp
    return inter * (1.0 - keep) + extra * keep


def softmax_scale(c: Dict) -> float:
    rs = c["rope_scaling"]
    m = _mscale(float(rs["factor"]), float(rs["mscale_all_dim"]))
    return (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5 * m * m


def rope(x, pos, inv_freq, mscale):
    """x (B, T, H, d) with interleaved rotation pairs, rotated at absolute
    positions pos (T,); returned in the rotate-half layout."""
    b, t, h, d = x.shape
    x = x.reshape(b, t, h, d // 2, 2).swapaxes(-1, -2).reshape(b, t, h, d)
    ang = pos[:, None].astype(jnp.float32) * inv_freq[None, :]
    emb = jnp.concatenate([ang, ang], -1)
    cos = (jnp.cos(emb) * mscale)[:, None, :]
    sin = (jnp.sin(emb) * mscale)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


@functools.partial(jax.jit, static_argnames=("dims", "fp8"))
def _attention(x, p, inv_freq, *, dims, fp8):
    """x += MLA(RMSNorm(x)), un-absorbed."""
    heads, nope, rope_d, rank, vd, eps, scale, msc = dims
    b, t, _ = x.shape
    pos = jnp.arange(t)
    h = rms(x, p["attn_norm"], eps)
    q = mm(h, p["wq"], fp8).reshape(b, t, heads, nope + rope_d)
    kv = mm(h, p["wkv_a"], fp8)
    c = rms(kv[..., :rank], p["kv_norm"], eps)
    q_pe = rope(q[..., nope:], pos, inv_freq, msc)
    k_pe = rope(kv[..., None, rank:], pos, inv_freq, msc)
    kvb = mm(c, p["wkv_b"], fp8).reshape(b, t, heads, nope + vd)
    q = jnp.concatenate([q[..., :nope], q_pe], -1)
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_pe, (b, t, heads, rope_d))], -1)
    v = kvb[..., nope:]
    blk = min(t, QUERY_BLOCK)

    def attend(args):
        qi, i = args
        s = jnp.einsum("bqhd,bshd->bhqs", qi, k, precision=HI) * scale
        causal = pos[None, :] <= (i * blk + jnp.arange(blk))[:, None]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        return jnp.einsum("bhqs,bshd->bqhd", jax.nn.softmax(s, -1), v,
                          precision=HI)

    qb = jnp.moveaxis(q.reshape(b, t // blk, blk, heads, nope + rope_d), 1, 0)
    a = jax.lax.map(attend, (qb, jnp.arange(t // blk)))
    a = jnp.moveaxis(a, 0, 1).reshape(b, t, heads * vd)
    return x + mm(a, p["wo"], fp8)


@functools.partial(jax.jit, static_argnames=("fp8",))
def _swiglu(h, wg, wi, wo, *, fp8):
    return mm(jax.nn.silu(mm(h, wg, fp8)) * mm(h, wi, fp8), wo, fp8)


@functools.partial(jax.jit, static_argnames=("k", "norm", "fp8"))
def _route(h, router, scaling, *, k, norm, fp8):
    """(gates (T, k), experts (T, k)) of flat tokens h (T, d)."""
    probs = jax.nn.softmax(mm(h, router, fp8), -1)
    g, e = jax.lax.top_k(probs, k)
    if norm:
        g = g / jnp.sum(g, -1, keepdims=True)
    return g * scaling, e


@functools.partial(jax.jit, static_argnames=("fp8",))
def _expert_add(y, h, gates, rows, slots, wg, wi, wo, *, fp8):
    """y[rows] += gate · FFN_e(h[rows]); rows == len(h) is padding."""
    t = h.shape[0]
    ok = rows < t
    r = jnp.minimum(rows, t - 1)
    out = _swiglu(h[r], wg, wi, wo, fp8=fp8)
    g = jnp.where(ok, gates[r, slots], 0.0)
    return y.at[rows].add(out * g[:, None], mode="drop")


@jax.jit
def _add(x, y):
    return x + y


def _mlp_norm(c, w, part, layer):
    d, dt = c["hidden_size"], c["torch_dtype"]
    return (w.leaf(f"/{part}/mlp_norm/scale", layer, (d,), dt),
            float(c["rms_norm_eps"]))


@jax.jit
def _rms(x, scale, eps):
    return rms(x, scale, eps)


def _matrix(c, w, path, layer, d_in, d_out, index=-1):
    """A matrix of the program's params: ternary (dequantized) where the
    group divides its contraction, else the bf16 leaf the program keeps."""
    grp = c["quantization"]["group_size"]
    if d_in % grp == 0:
        return w.matrix(path, layer, d_in, d_out, grp, index)
    return w.leaf(path, layer, (d_in, d_out), c["torch_dtype"])


def _moe(c, w, layer, h, fp8):
    """Σ_e gate_e · FFN_e(h) + FFN_shared(h) over flat tokens h (T, d)."""
    d, fe = c["hidden_size"], c["moe_intermediate_size"]
    n_e, k = c["n_routed_experts"], c["num_experts_per_tok"]
    base = "/blocks/b0/moe"
    router = w.leaf(f"{base}/router/kernel", layer, (d, n_e), "float32")
    gates, experts = _route(h, router, float(c["routed_scaling_factor"]),
                            k=k, norm=bool(c["norm_topk_prob"]), fp8=fp8)
    experts = np.asarray(experts)
    sh = c["n_shared_experts"] * fe
    S = lambda n, i, o: _matrix(c, w, f"{base}/shared/{n}/kernel", layer, i, o)
    y = _swiglu(h, S("wg", d, sh), S("wi", d, sh), S("wo", sh, d), fp8=fp8)
    t = h.shape[0]
    for e in range(n_e):
        rows, slots = np.nonzero(experts == e)
        if not rows.size:
            continue
        n = 1 << max(4, (rows.size - 1).bit_length())   # one shape per 2^j
        pad = n - rows.size
        rows = np.concatenate([rows, np.full(pad, t)]).astype(np.int32)
        slots = np.concatenate([slots, np.zeros(pad)]).astype(np.int32)
        X = lambda m, i, o: _matrix(c, w, f"{base}/experts/{m}/kernel",
                                    layer, i, o, index=e)
        y = _expert_add(y, h, gates, rows, slots, X("wg", d, fe),
                        X("wi", d, fe), X("wo", fe, d), fp8=fp8)
    return y


def _dims(c: Dict):
    rs = c["rope_scaling"]
    msc = (_mscale(float(rs["factor"]), float(rs["mscale"]))
           / _mscale(float(rs["factor"]), float(rs["mscale_all_dim"])))
    return (c["num_attention_heads"], c["qk_nope_head_dim"],
            c["qk_rope_head_dim"], c["kv_lora_rank"], c["v_head_dim"],
            float(c["rms_norm_eps"]), softmax_scale(c), msc)


def _attn_params(c, w, part, layer):
    d, dt = c["hidden_size"], c["torch_dtype"]
    heads, nope, rope_d, rank, vd = _dims(c)[:5]
    M = lambda n, i, o: _matrix(c, w, f"/{part}/attn/{n}/kernel", layer, i, o)
    return {
        "attn_norm": w.leaf(f"/{part}/attn_norm/scale", layer, (d,), dt),
        "kv_norm": w.leaf(f"/{part}/attn/kv_norm/scale", layer, (rank,), dt),
        "wq": M("wq", d, heads * (nope + rope_d)),
        "wkv_a": M("wkv_a", d, rank + rope_d),
        "wkv_b": M("wkv_b", rank, heads * (nope + vd)),
        "wo": M("wo", heads * vd, d),
    }


@jax.jit
def _embed(table, tokens):
    return jnp.take(table, tokens, axis=0)


def final_hidden(c: Dict, w, tokens, fp8: bool = False):
    """(B, T) token ids -> (B, T, d) final-normed hidden states, f32, with
    the weights that ``w`` (``weights.Seeded``) draws."""
    d, v = c["hidden_size"], c["vocab_size"]
    dims = _dims(c)
    inv_freq = jnp.asarray(yarn_inv_freq(c), jnp.float32)
    table = w.leaf("/embed/embedding", -1, (v, d), c["torch_dtype"])
    x = _embed(table, jnp.asarray(tokens))
    del table
    b, t, _ = x.shape
    first = c["first_k_dense_replace"]
    for i in range(c["num_hidden_layers"]):
        part, layer = (f"prefix/p{i}", -1) if i < first else ("blocks/b0",
                                                               i - first)
        x = _attention(x, _attn_params(c, w, part, layer), inv_freq,
                       dims=dims, fp8=fp8)
        scale, eps = _mlp_norm(c, w, part, layer)
        h = _rms(x, scale, eps).reshape(b * t, d)
        if i < first:
            ff = c["intermediate_size"]
            M = lambda n, i_, o: _matrix(c, w, f"/{part}/mlp/{n}/kernel",
                                         layer, i_, o)
            y = _swiglu(h, M("wg", d, ff), M("wi", d, ff), M("wo", ff, d),
                        fp8=fp8)
        else:
            y = _moe(c, w, layer, h, fp8)
        x = _add(x, y.reshape(b, t, d))
    scale = w.leaf("/final_norm/scale", -1, (d,), c["torch_dtype"])
    return _rms(x, scale, float(c["rms_norm_eps"]))


def head(c: Dict, w):
    """The output head Ŵ (d, V), f32."""
    return w.matrix("/lm_head/kernel", -1, c["hidden_size"], c["vocab_size"],
                    c["quantization"]["group_size"])
