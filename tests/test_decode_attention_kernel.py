"""Fused int8-KV flash-decode attention kernel vs jnp oracle (§Perf it. 5)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref


def _case(b, s, kv, g, hd, filled, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, kv, g, hd), dtype=np.float32))
    k8 = jnp.asarray(rng.integers(-127, 128, (b, s, kv, hd)), jnp.int8)
    v8 = jnp.asarray(rng.integers(-127, 128, (b, s, kv, hd)), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.005, 0.02, (b, s, kv)).astype(np.float32))
    vs = jnp.asarray(rng.uniform(0.005, 0.02, (b, s, kv)).astype(np.float32))
    pb = np.full((b, s), -1, np.int32)
    pb[:, :filled] = np.arange(filled)
    pos = jnp.asarray(rng.integers(filled - 8, filled, (b,)), jnp.int32)
    return q, k8, ks, v8, vs, jnp.asarray(pb), pos


@pytest.mark.parametrize("b,s,kv,g,hd,filled", [
    (1, 256, 1, 1, 64, 200),     # MHA corner, partially filled ring
    (2, 1024, 2, 4, 64, 700),    # GQA
    (3, 512, 4, 2, 128, 512),    # full ring, hd=128
    (2, 768, 1, 8, 64, 100),     # non-pow2 S, mostly empty
])
@pytest.mark.parametrize("window", [None, 128])
def test_matches_oracle(b, s, kv, g, hd, filled, window):
    args = _case(b, s, kv, g, hd, filled, seed=s)
    y_k = decode_attention(*args, window=window)
    y_r = decode_attention_ref(*args, window=window)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=1e-5, atol=1e-5)


def test_matches_model_int8_decode_path():
    """Kernel semantics == the in-model int8 decode attention math."""
    from repro import configs
    from repro.models import attention as attn
    from repro.models import init_params

    cfg = configs.get_smoke_config("qwen2-1.5b").scaled(
        kv_cache_dtype="int8")
    params = init_params(cfg, jax.random.PRNGKey(0))
    p = jax.tree.map(lambda a: a[0], params["blocks"]["b0"])["attn"]
    b, cap = 2, 32
    cache = attn.cache_init(cfg, b, cap, None, jnp.float32)
    rng = np.random.default_rng(1)
    pos0 = jnp.zeros((b,), jnp.int32)
    cache2 = cache
    xs = [jnp.asarray(rng.standard_normal((b, cfg.d_model), np.float32) * .1)
          for _ in range(5)]
    for t, x_t in enumerate(xs):
        y_model, cache2 = attn.attention_decode(
            p, cfg, cache2, x_t, jnp.full((b,), t, jnp.int32))
    # replay the last step through the kernel
    from repro.models.common import apply_rope, dense

    x_t = xs[-1]
    pos = jnp.full((b,), 4, jnp.int32)
    hd, kv = cfg.head_dim, cfg.n_kv_heads
    g = cfg.n_heads // kv
    q = dense(p["wq"], x_t).reshape(b, cfg.n_heads, hd)
    q = apply_rope(q, pos, cfg.rope_theta).reshape(b, kv, g, hd)
    # the model's ring storage, in the kernel's (B, S, KV, hd) layout
    from repro.kernels.chunk_attention.ref import ring_layer
    k8, ks, v8, vs, pb = ring_layer(
        *(cache2[n][None] for n in ("k", "k_scale", "v", "v_scale", "pos")),
        0, kv)
    y_kern = decode_attention(q.astype(jnp.float32), k8, ks, v8, vs, pb, pos)
    y_kern = dense(p["wo"], y_kern.reshape(b, cfg.n_heads * hd))
    np.testing.assert_allclose(np.asarray(y_kern), np.asarray(y_model),
                               rtol=5e-3, atol=5e-3)
