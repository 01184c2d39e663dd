"""Fused int8-KV flash-decode attention (Pallas, TPU target).

One grid program per (batch, kv-head). The int8 cache block (S, hd) and its
scales live in VMEM; the kernel walks the cache in chunks with an online-
softmax accumulator, dequantizing int8→f32 IN-REGISTER — the HBM traffic is
exactly the packed int8 bytes + scales + q/out, i.e. the §Perf iteration-5
streaming floor. Scores (G, C) stay in VMEM (never (G, S)).

VMEM budget per program (hd=128, C=512): k8+v8 chunks via the resident
(S, hd) int8 blocks — 2·S·hd B; at S=32k, hd=128 that is 8 MB + scales,
inside the ~16 MB v5e VMEM. Longer caches shard S over the mesh first
(partition.state_pspecs) so per-chip S stays bounded.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k8_ref, ks_ref, v8_ref, vs_ref, posb_ref, pos_ref,
            o_ref, *, chunk: int, scale: float, w_eff: int):
    # block shapes carry leading singleton (batch, kv) dims — index them away
    g, hd = q_ref.shape[-2:]
    s = k8_ref.shape[1]
    n_chunks = s // chunk
    q = q_ref[0, 0].astype(jnp.float32) * scale         # (G, hd)
    pos = pos_ref[0]

    def body(i, carry):
        m, l, acc = carry
        off = i * chunk
        k8 = k8_ref[0, pl.dslice(off, chunk), 0, :]
        ks = ks_ref[0, pl.dslice(off, chunk), 0]
        pb = posb_ref[0, pl.dslice(off, chunk)]
        k = k8.astype(jnp.float32) * ks[:, None]        # (C, hd) dequant
        logits = q @ k.T                                # (G, C)
        valid = (pb >= 0) & (pb <= pos) & (pos - pb < w_eff)
        logits = jnp.where(valid[None, :], logits, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))     # (G,)
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[:, None])                 # (G, C)
        v8 = v8_ref[0, pl.dslice(off, chunk), 0, :]
        vs = vs_ref[0, pl.dslice(off, chunk), 0]
        v = v8.astype(jnp.float32) * vs[:, None]             # (C, hd)
        acc = acc * alpha[:, None] + p @ v
        l = l * alpha + jnp.sum(p, axis=-1)
        return m_new, l, acc

    m0 = jnp.full((g,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((g,), jnp.float32)
    acc0 = jnp.zeros((g, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_chunks, body, (m0, l0, acc0))
    o_ref[0, 0] = acc / jnp.maximum(l, 1e-30)[:, None]


def decode_attention_pallas(q, k8, k_scale, v8, v_scale, pos_buf, pos, *,
                            window=None, chunk: int = 512,
                            interpret: bool):
    """Same contract as ref.decode_attention_ref; returns (B, KV, G, hd) f32.

    Grid (B, KV); per-program blocks: q (G, hd), cache (S, hd) int8 + (S,)
    scales, pos_buf (S,), pos scalar.
    """
    b, s, kv, hd = k8.shape
    g = q.shape[2]
    c = min(chunk, s)
    while s % c:
        c -= 1
    w_eff = window if window else s + 1
    scale = hd ** -0.5

    kern = functools.partial(_kernel, chunk=c, scale=scale, w_eff=w_eff)
    return pl.pallas_call(
        kern,
        grid=(b, kv),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda i, j: (i, j, 0, 0)),   # q
            pl.BlockSpec((1, s, 1, hd), lambda i, j: (i, 0, j, 0)),   # k8
            pl.BlockSpec((1, s, 1), lambda i, j: (i, 0, j)),          # ks
            pl.BlockSpec((1, s, 1, hd), lambda i, j: (i, 0, j, 0)),   # v8
            pl.BlockSpec((1, s, 1), lambda i, j: (i, 0, j)),          # vs
            pl.BlockSpec((1, s), lambda i, j: (i, 0)),                # pos_buf
            pl.BlockSpec((1,), lambda i, j: (i,)),                    # pos
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, hd), jnp.float32),
        interpret=interpret,
    )(q, k8, k_scale, v8, v_scale, pos_buf, pos)
