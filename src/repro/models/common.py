"""Shared building blocks: dense (with PTQTP dispatch), RMSNorm, RoPE, init.

The framework is pure-functional JAX: params are nested dicts of arrays,
modules are (init, apply) function pairs. A dense layer's ``kernel`` leaf may
be replaced post-training by a ``QuantizedKernel`` (two packed trit-planes +
group scales); ``dense`` dispatches on the leaf type, so *every* model in the
zoo serves quantized without architectural change — the paper's
model-agnosticity claim, made structural.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import threading
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quantize_model import QuantizedKernel

_state = threading.local()


def matmul_backend() -> str:
    """Active quantized-matmul backend. Defaults to 'auto': the Pallas hand
    kernels (small-m decode fast path included) on TPU, XLA grouped on CPU."""
    return getattr(_state, "backend", "auto")


@contextlib.contextmanager
def use_matmul_backend(backend: str):
    """Select the quantized-matmul backend ('auto'|'grouped'|'pallas'|'ref')."""
    prev = matmul_backend()
    _state.backend = backend
    try:
        yield
    finally:
        _state.backend = prev


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, *, bias: bool = False,
               dtype=jnp.float32, scale: Optional[float] = None) -> Dict[str, Any]:
    std = scale if scale is not None else (1.0 / np.sqrt(d_in))
    p = {"kernel": (jax.random.normal(key, (d_in, d_out)) * std).astype(dtype)}
    if bias:
        p["bias"] = jnp.zeros((d_out,), dtype)
    return p


def dense(params: Dict[str, Any], x: jax.Array) -> jax.Array:
    """y = x @ kernel (+ bias); kernel may be a QuantizedKernel."""
    k = params["kernel"]
    if isinstance(k, QuantizedKernel):
        from repro.kernels.ternary_matmul.ops import ternary_matmul

        y = ternary_matmul(
            x, k.t1p, k.t2p, k.alpha,
            group_size=k.group_size, backend=matmul_backend(),
            out_dtype=x.dtype,
        )
    else:
        y = jnp.einsum("...d,df->...f", x, k.astype(x.dtype))
    if "bias" in params:
        y = y + params["bias"].astype(y.dtype)
    return y


def norm_init(d: int, dtype=jnp.float32) -> Dict[str, Any]:
    return {"scale": jnp.ones((d,), dtype)}


def rms_norm(params: Dict[str, Any], x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class YarnRope:
    """YaRN rotary scaling as DeepSeek-V2's modelling code computes it
    (``DeepseekV2YarnRotaryEmbedding``, from the config's ``rope_scaling``
    block): inverse frequencies blended between the interpolated and the
    original ones over a linear ramp of rope dims, cos/sin scaled by
    mscale(factor, mscale) / mscale(factor, mscale_all_dim), and the
    attention softmax scale multiplied by mscale(factor, mscale_all_dim)²."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def yarn_mscale(scale: float, mscale: float = 1.0) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_correction_range(y: YarnRope, dim: int, theta: float):
    """(low, high) rope dims of the ramp: dims below ``low`` keep the
    original frequency, dims above ``high`` take the interpolated one."""
    def corr(rotations):
        return (dim * math.log(y.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = math.floor(corr(y.beta_fast))
    high = math.ceil(corr(y.beta_slow))
    return max(low, 0), min(high, dim - 1)


def rope_freqs(head_dim: int, theta: float,
               yarn: Optional[YarnRope] = None) -> jax.Array:
    half = head_dim // 2
    if yarn is None:
        return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32)
                                / half))
    extra = 1.0 / (theta ** (np.arange(0, half, dtype=np.float64) / half))
    inter = extra / yarn.factor
    low, high = yarn_correction_range(yarn, head_dim, theta)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low),
                   0.0, 1.0)
    keep = 1.0 - ramp                     # 1: original frequency
    return jnp.asarray(inter * (1.0 - keep) + extra * keep, jnp.float32)


def rope_scale(yarn: Optional[YarnRope]) -> float:
    """The factor YaRN puts on cos and sin (1 where both mscales agree)."""
    if yarn is None:
        return 1.0
    return (yarn_mscale(yarn.factor, yarn.mscale)
            / yarn_mscale(yarn.factor, yarn.mscale_all_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float, *,
               yarn: Optional[YarnRope] = None,
               interleave: bool = False) -> jax.Array:
    """x: (..., S, H, hd) or (..., H, hd) with positions (..., S) / (...,).

    ``interleave``: rotation pairs are adjacent dims (2i, 2i + 1), as in
    DeepSeek-V2's checkpoints; they are gathered into the rotate-half
    layout first (both q and k alike, so their dot products are those of
    the interleaved rotation), and the result stays in that layout."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta, yarn)  # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    m = rope_scale(yarn)
    if m != 1.0:
        cos, sin = cos * m, sin * m
    cos, sin = cos[..., None, :], sin[..., None, :]  # broadcast over heads
    xf = x.astype(jnp.float32)
    if interleave:
        xf = jnp.swapaxes(xf.reshape(xf.shape[:-1] + (hd // 2, 2)), -1,
                          -2).reshape(xf.shape)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def dtype_of(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]
