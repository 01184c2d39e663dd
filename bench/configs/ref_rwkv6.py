"""Plain reference of an RWKV-6 "Finch" model (model_type ``rwkv6``).

Straight ``jax.numpy`` in float32 at HIGHEST matmul precision: the whole
sequence per layer, the WKV recurrence as a plain scan over time from a
zero state. It imports nothing of the program under test; its weights
come from the seed through the harness (``weights.Seeded``: the seed
under the architecture's leaf rules).

Layer equations (Finch, arXiv:2404.05892), as the program states them:

  time mix, on h = RMSNorm(x) · g_time, with h₋₁ the previous token's h
  (zero before the first):
    s   = h₋₁ − h
    a   = tanh((h + s ⊙ μ_x) A) reshaped (5, 32);  m_j = h + s ⊙ (μ_j + a_j B_j)
          for j in (w, k, v, r, g)
    r, k, v = m_r W_r, m_k W_k, m_v W_v;  g = silu(m_g W_g)
    w   = exp(−exp(w₀ + tanh(m_w D_a) D_b))                (per channel)
    per head:  y_t = r_tᵀ (S + diag(u) k_t v_tᵀ);  S ← diag(w_t) S + k_t v_tᵀ
    x  += (GroupNorm_heads(y) · g_ln ⊙ g) W_o
  channel mix, on h = RMSNorm(x) · g_chan, s = h₋₁ − h:
    x  += σ((h + s ⊙ μ_r) W_cr) ⊙ (relu((h + s ⊙ μ_k) W_ck)² W_cv)
  logits = RMSNorm(x) · g_final · W_head

Departures from the published model, as served: RMSNorm in place of
LayerNorm, no input LayerNorm (ln0), group-norm eps 1e-5.

``fp8=True`` is the control: every matmul's operands are cast to
float8_e4m3fn with one absmax scale per tensor, accumulated in f32.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
MIX = ("w", "k", "v", "r", "g")
LORA_R, DECAY_R = 32, 64


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


def _shifted(h):
    return jnp.concatenate([jnp.zeros_like(h[:, :1]), h[:, :-1]], axis=1)


@functools.partial(jax.jit, static_argnames=("dims", "fp8"))
def _layer(x, p, *, dims, fp8):
    heads, hd, eps = dims
    b, t, d = x.shape
    # ---- time mix
    h = rms(x, p["time_norm"], eps)
    s = _shifted(h) - h
    a = jnp.tanh(mm(h + s * p["mu_x"], p["mix_lora_a"], fp8))
    a = a.reshape(b, t, len(MIX), LORA_R)
    m = {}
    for j, name in enumerate(MIX):
        adj = mm(a[:, :, j], p["mix_lora_b"][j], fp8)
        m[name] = h + s * (p["mu"][j] + adj)
    r = mm(m["r"], p["wr"], fp8).reshape(b, t, heads, hd)
    k = mm(m["k"], p["wk"], fp8).reshape(b, t, heads, hd)
    v = mm(m["v"], p["wv"], fp8).reshape(b, t, heads, hd)
    g = jax.nn.silu(mm(m["g"], p["wg"], fp8))
    lo = mm(jnp.tanh(mm(m["w"], p["decay_lora_a"], fp8)), p["decay_lora_b"],
            fp8)
    w = jnp.exp(-jnp.exp(p["decay_base"] + lo)).reshape(b, t, heads, hd)
    u = p["u"]

    def step(S, inp):
        rt, kt, vt, wt = inp                                   # (b, H, hd)
        kv = kt[..., :, None] * vt[..., None, :]               # (b, H, i, j)
        y = jnp.einsum("bhi,bhij->bhj", rt, S + u[None, :, :, None] * kv,
                       precision=HI)
        return wt[..., None] * S + kv, y

    S0 = jnp.zeros((b, heads, hd, hd), jnp.float32)
    _, y = jax.lax.scan(step, S0, tuple(jnp.moveaxis(z, 1, 0)
                                        for z in (r, k, v, w)))
    y = jnp.moveaxis(y, 0, 1)                                  # (b, t, H, hd)
    mu_y = jnp.mean(y, -1, keepdims=True)
    var_y = jnp.mean((y - mu_y) ** 2, -1, keepdims=True)
    y = ((y - mu_y) * jax.lax.rsqrt(var_y + 1e-5)).reshape(b, t, d)
    x = x + mm(y * p["ln_x"] * g, p["wo"], fp8)
    # ---- channel mix
    h = rms(x, p["chan_norm"], eps)
    s = _shifted(h) - h
    kk = jnp.square(jax.nn.relu(mm(h + s * p["mu_k"], p["cwk"], fp8)))
    rr = jax.nn.sigmoid(mm(h + s * p["mu_r"], p["cwr"], fp8))
    return x + rr * mm(kk, p["cwv"], fp8)


def _dims(c: Dict):
    hd = c["head_size"]
    return (c["hidden_size"] // hd, hd, float(c["layer_norm_epsilon"]))


def _layer_params(c: Dict, w, layer: int):
    d, ff = c["hidden_size"], c["intermediate_size"]
    heads, hd, _ = _dims(c)
    grp = c["quantization"]["group_size"]
    dt = c["torch_dtype"]
    W = lambda path, i, o: w.matrix(f"/blocks/b0/{path}/kernel", layer, i,
                                    o, grp)
    V = lambda path, shape: w.leaf(f"/blocks/b0/{path}", layer, shape, dt)
    return {
        "time_norm": V("time_norm/scale", (d,)),
        "chan_norm": V("chan_norm/scale", (d,)),
        "mu_x": V("time/mu_x", (d,)), "mu": V("time/mu", (len(MIX), d)),
        "mix_lora_a": V("time/mix_lora_a", (d, len(MIX) * LORA_R)),
        "mix_lora_b": V("time/mix_lora_b", (len(MIX), LORA_R, d)),
        "decay_base": V("time/decay_base", (d,)),
        "decay_lora_a": V("time/decay_lora_a", (d, DECAY_R)),
        "decay_lora_b": V("time/decay_lora_b", (DECAY_R, d)),
        "u": V("time/u", (heads, hd)), "ln_x": V("time/ln_x/scale", (d,)),
        "wr": W("time/wr", d, d), "wk": W("time/wk", d, d),
        "wv": W("time/wv", d, d), "wg": W("time/wg", d, d),
        "wo": W("time/wo", d, d),
        "mu_k": V("chan/mu_k", (d,)), "mu_r": V("chan/mu_r", (d,)),
        "cwk": W("chan/wk", d, ff), "cwv": W("chan/wv", ff, d),
        "cwr": W("chan/wr", d, d),
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
    return rms(x, scale, float(c["layer_norm_epsilon"]))


def head(c: Dict, w):
    """The output head Ŵ (d, V), f32."""
    return w.matrix("/lm_head/kernel", -1, c["hidden_size"], c["vocab_size"],
                    c["quantization"]["group_size"])
