"""Jitted public wrapper for the PTQTP ternary matmul.

Backends:
  * ``auto``    — platform-aware selection (the default): the Pallas hand
                  kernel compiled on TPU, the XLA ``grouped`` path elsewhere.
  * ``pallas``  — the fused TPU kernel, compiled on TPU and run by the
                  Pallas interpreter elsewhere (the platform alone decides;
                  interpret mode is for validation only, never for serving).
                  Decode batches (m < 128) stay resident in one m block
                  with no padding to MXU tiles; larger m is tiled by 128,
                  and n is padded up to a multiple of 128 lanes.
  * ``grouped`` — XLA path over *packed* planes: unpack + grouped einsum.
                  This is what the multi-pod dry-run lowers, and is what XLA
                  itself would fuse on TPU absent the hand kernel.
  * ``ref``     — full-dequant oracle (testing only).

The grouped einsum applies α to per-group partial sums, never materializing
the dequantized Ŵ at matmul precision for the whole matrix at once:

  y[b, n] = Σ_g α¹[n,g]·(Σ_{j∈g} x[b,j]·T¹[n,j]) + α²[...]·(...)

Tile selection is shape-cached (`_select_tiles`): block sizes are pure
functions of (m, n) and the per-shape answer is memoized so the dispatch
adds no per-call Python cost on the decode hot path.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.core.packing import pack_trits, unpack_trits
from repro.kernels.ternary_matmul import ref as _ref
from repro.kernels.ternary_matmul.kernel import ternary_matmul_pallas

DEFAULT_BACKEND = "auto"
# Below this m the batch is decode-shaped: padding to a 128-row MXU tile
# would waste > (1 - m/128) of every pass, so all m rows form one block.
SMALL_M_THRESHOLD = 128
# Widest output block: the TPU needs a block's last dim to be a multiple of
# 128 lanes, and each block holds bn · d/4 packed bytes.
_MAX_BLOCK_N = 256


def resolve_backend(backend: str | None = None, platform: str | None = None) -> str:
    """Map 'auto'/None to the fastest backend for the current platform."""
    if backend in (None, "auto"):
        platform = platform or jax.default_backend()
        return "pallas" if platform == "tpu" else "grouped"
    return backend


@functools.lru_cache(maxsize=None)
def _largest_divisor_at_most(n: int, cap: int) -> int:
    """Largest divisor of n that is <= cap.

    Fast paths: gcd catches every n with a divisor structure aligned to cap
    (cap itself, and — cap being a power of two — the full 2-adic part of n
    via the n & -n bit trick folded into gcd).  The general case enumerates
    divisor pairs in O(√n) instead of the seed's linear countdown scan.
    Memoized: tile selection asks once per weight shape.
    """
    if n <= cap:
        return n
    g = math.gcd(n, cap)
    if g == cap:
        return cap
    best = g  # gcd(n, pow2-cap) == min(n & -n, cap): the bit-trick lower bound
    i = 1
    while i * i <= n:
        if n % i == 0:
            for d in (i, n // i):
                if best < d <= cap:
                    best = d
        i += 1
    return best


@functools.lru_cache(maxsize=None)
def _select_tiles(m: int, n: int) -> tuple:
    """Per-shape (block_m, block_n) choice, memoized.

    block_n is a multiple of 128 lanes dividing n rounded up to 128 (the
    caller pads the weight rows to that); block_m is all of a decode
    batch, else the 128-row MXU tile with the residual rows padded by the
    caller.
    """
    bm = m if m < SMALL_M_THRESHOLD else 128
    return bm, 128 * _largest_divisor_at_most(-(-n // 128),
                                              _MAX_BLOCK_N // 128)


def _grouped(x, t1p, t2p, alpha, group_size):
    *lead, d = x.shape
    n = t1p.shape[0]
    g = group_size
    ng = d // g
    xf = x.reshape(-1, ng, g)
    if t1p.dtype == jnp.uint8:  # packed: 4 trits / byte
        t1, t2 = unpack_trits(t1p), unpack_trits(t2p)
    else:  # pre-unpacked int8 planes (the decode loop hoists the unpack)
        t1, t2 = t1p, t2p
    t1 = t1.reshape(n, ng, g).astype(x.dtype)
    t2 = t2.reshape(n, ng, g).astype(x.dtype)
    # (B, ng, g) x (n, ng, g) -> (B, ng, n) partial sums per group
    p1 = jnp.einsum("bgk,ngk->bgn", xf, t1, preferred_element_type=jnp.float32)
    p2 = jnp.einsum("bgk,ngk->bgn", xf, t2, preferred_element_type=jnp.float32)
    a = alpha.astype(jnp.float32)
    y = jnp.einsum("bgn,ng->bn", p1, a[..., 0]) + jnp.einsum(
        "bgn,ng->bn", p2, a[..., 1]
    )
    return y.reshape(*lead, n)


def _pallas(x, t1p, t2p, alpha, group_size, *, interpret):
    *lead, d = x.shape
    x2 = x.reshape(-1, d)
    m = x2.shape[0]
    n = t1p.shape[0]
    bm, bn = _select_tiles(m, n)
    pad_m, pad_n = (-m) % bm, (-n) % bn
    if pad_m:
        x2 = jnp.pad(x2, ((0, pad_m), (0, 0)))
    if pad_n:  # zero rows: trits 0, α 0 -> output columns sliced off below
        t1p, t2p = (jnp.pad(t, ((0, pad_n), (0, 0))) for t in (t1p, t2p))
        alpha = jnp.pad(alpha, ((0, pad_n), (0, 0), (0, 0)))
    y = ternary_matmul_pallas(
        x2, t1p, t2p, alpha, group_size=group_size, block_m=bm, block_n=bn,
        interpret=interpret)
    return y[:m, :n].reshape(*lead, n)


def ternary_matmul(
    x: jax.Array,
    t1p: jax.Array,
    t2p: jax.Array,
    alpha: jax.Array,
    *,
    group_size: int = 128,
    backend: str = DEFAULT_BACKEND,
    out_dtype=None,
) -> jax.Array:
    """y = x @ Ŵᵀ. x: (..., d); packed planes (n, d//4); alpha (n, d//G, 2).

    ``backend='auto'`` selects Pallas (compiled) on TPU and the grouped XLA
    path elsewhere; an explicit ``backend='pallas'`` off-TPU validates
    through the interpreter.

    Plane dtype doubles as the storage tag: uint8 means packed (4 trits per
    byte, what every backend expects), int8 means raw ±1/0 trits that a
    caller already unpacked (the serving decode loop hoists the unpack out
    of its scan) — only the grouped einsum consumes those directly.
    """
    if t1p.dtype != jnp.uint8:
        # Raw planes: only the grouped einsum consumes them. 'auto' adapts;
        # an explicit ask for another backend is a misconfiguration (e.g.
        # preunpack_decode=True on TPU would silently bypass the hand
        # kernel), so fail loudly instead of overriding the choice.
        if backend not in (None, "auto", "grouped"):
            raise ValueError(
                f"backend {backend!r} requires packed uint8 trit-planes; "
                "pre-unpacked int8 planes are served by the grouped backend")
        backend = "grouped"
    else:
        backend = resolve_backend(backend)
    if backend == "ref":
        y = _ref.ternary_matmul_packed_ref(x, t1p, t2p, alpha, group_size)
    elif backend == "grouped":
        y = _grouped(x, t1p, t2p, alpha, group_size)
    elif backend == "pallas":
        y = _pallas(x, t1p, t2p, alpha, group_size,
                    interpret=jax.default_backend() != "tpu")
    else:
        raise ValueError(f"unknown backend {backend!r}")
    return y.astype(out_dtype) if out_dtype is not None else y


def quantized_from_dense(w_t: jax.Array, alpha: jax.Array):
    """Pack int8 planes -> uint8 packed buffers. w_t: tuple (t1, t2)."""
    t1, t2 = w_t
    return pack_trits(t1), pack_trits(t2)
