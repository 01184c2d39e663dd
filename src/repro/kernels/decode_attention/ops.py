"""Public wrapper for fused int8-KV decode attention."""

from __future__ import annotations

import jax

from repro.kernels.decode_attention import ref as _ref
from repro.kernels.decode_attention.kernel import decode_attention_pallas


def decode_attention(q, k8, k_scale, v8, v_scale, pos_buf, pos, *,
                     window=None, backend: str = "pallas"):
    """(B, KV, G, hd) f32 decode attention over an int8 ring cache."""
    if backend == "ref":
        return _ref.decode_attention_ref(q, k8, k_scale, v8, v_scale,
                                         pos_buf, pos, window=window)
    return decode_attention_pallas(q, k8, k_scale, v8, v_scale,
                                   pos_buf, pos, window=window,
                                   interpret=jax.default_backend() != "tpu")
