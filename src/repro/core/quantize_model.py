"""Model-tree quantization: walk a params pytree, quantize every linear kernel.

The paper's deployment recipe ("all linear layers were quantized", Sec. 4.1):
every 2-D dense kernel — and every scan-stacked (L, in, out) kernel — becomes a
``QuantizedKernel`` (two packed trit-planes + group scales). Embedding gathers,
norms, biases, routers, and vector-sized recurrence parameters stay FP
(DESIGN.md §4). Model-agnostic: the walk needs no architecture knowledge.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ptqtp
from repro.core.packing import pack_trits, ptqtp_weight_bytes

EXCLUDE_SUBSTRINGS = ("embed", "router", "norm", "decay", "lora", "conv", "rglru")


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedKernel:
    """PTQTP replacement for a dense kernel of logical shape (d_in, d_out).

    Stored transposed (output-major) to match the quantizer/matmul layout:
      t1p, t2p : (d_out, d_in // 4) uint8 packed trit-planes
      alpha    : (d_out, d_in // G, 2) fp
    Stacked kernels carry an extra leading layer dim on every buffer.
    """

    t1p: jax.Array
    t2p: jax.Array
    alpha: jax.Array
    d_in: int
    d_out: int
    group_size: int

    def tree_flatten(self):
        return (self.t1p, self.t2p, self.alpha), (self.d_in, self.d_out,
                                                  self.group_size)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def logical_shape(self):
        return (self.d_in, self.d_out)

    def nbytes(self) -> int:
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in (self.t1p, self.t2p, self.alpha))


def quantize_kernel(kernel: jax.Array, cfg: ptqtp.PTQTPConfig) -> QuantizedKernel:
    """Quantize a (d_in, d_out) kernel; any leading dims (scan-stacked layers,
    MoE experts — e.g. (L, E, d_in, d_out)) are mapped over one matrix at a
    time, so the quantizer's working set is one matrix's, not the stack's."""
    lead = kernel.shape[:-2]
    d_in, d_out = kernel.shape[-2:]
    if lead:
        flat = kernel.reshape((-1,) + kernel.shape[-2:])
        t1p, t2p, alpha = jax.lax.map(lambda k: _quantize_2d(k, cfg), flat)
        t1p = t1p.reshape(lead + t1p.shape[1:])
        t2p = t2p.reshape(lead + t2p.shape[1:])
        alpha = alpha.reshape(lead + alpha.shape[1:])
    else:
        t1p, t2p, alpha = _quantize_2d(kernel, cfg)
    return QuantizedKernel(t1p, t2p, alpha, int(d_in), int(d_out), cfg.group_size)


def _quantize_2d(kernel: jax.Array, cfg: ptqtp.PTQTPConfig):
    # Quantizer layout: rows = outputs, groups along the contraction dim.
    q = ptqtp.ptqtp_quantize(kernel.T, cfg)
    return pack_trits(q.t1), pack_trits(q.t2), q.alpha


def dequantize_kernel(qk: QuantizedKernel, dtype=jnp.float32) -> jax.Array:
    """Back to a dense (d_in, d_out) kernel (testing / fallback path)."""
    from repro.core.packing import unpack_trits

    def deq(t1p, t2p, alpha):
        n, db = t1p.shape
        d = db * 4
        g = qk.group_size
        t1 = unpack_trits(t1p).reshape(n, d // g, g).astype(jnp.float32)
        t2 = unpack_trits(t2p).reshape(n, d // g, g).astype(jnp.float32)
        a = alpha.astype(jnp.float32)
        w = (t1 * a[..., 0:1] + t2 * a[..., 1:2]).reshape(n, d)
        return w.T  # (d_in, d_out)

    lead = qk.t1p.shape[:-2]
    if lead:
        flat = jax.vmap(deq)(
            qk.t1p.reshape((-1,) + qk.t1p.shape[-2:]),
            qk.t2p.reshape((-1,) + qk.t2p.shape[-2:]),
            qk.alpha.reshape((-1,) + qk.alpha.shape[-3:]))
        return flat.reshape(lead + flat.shape[1:]).astype(dtype)
    return deq(qk.t1p, qk.t2p, qk.alpha).astype(dtype)


@jax.jit
def relative_error(kernel: jax.Array, qk: QuantizedKernel) -> jax.Array:
    """||W − Ŵ||_F / ||W||_F, summed one matrix at a time so that a stacked
    kernel's dequantized copy is never whole in device memory."""
    def sums(args):
        w, t1p, t2p, alpha = args
        w = w.astype(jnp.float32)
        d = w - dequantize_kernel(
            dataclasses.replace(qk, t1p=t1p, t2p=t2p, alpha=alpha),
            jnp.float32)
        return jnp.sum(d * d), jnp.sum(w * w)

    flat = [a.reshape((-1,) + a.shape[a.ndim - k:]) for a, k in
            ((kernel, 2), (qk.t1p, 2), (qk.t2p, 2), (qk.alpha, 3))]
    err2, w2 = jax.lax.map(sums, flat)
    return (jnp.sqrt(jnp.sum(err2))
            / jnp.maximum(jnp.sqrt(jnp.sum(w2)), 1e-30))


def default_predicate(path: str, leaf: Any, group_size: int) -> bool:
    if not isinstance(leaf, jax.Array) and not isinstance(leaf, np.ndarray):
        return False
    if leaf.ndim < 2 or leaf.ndim > 4:
        return False
    lowered = path.lower()
    if any(s in lowered for s in EXCLUDE_SUBSTRINGS):
        return False
    if not lowered.endswith("kernel"):
        return False
    d_in = leaf.shape[-2]
    return d_in % group_size == 0 and d_in % 4 == 0


def quantize_tree(
    params: Dict[str, Any],
    cfg: Optional[ptqtp.PTQTPConfig] = None,
    predicate: Optional[Callable[[str, Any, int], bool]] = None,
) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Quantize every matching kernel in a nested-dict params tree.

    Returns (new_params, report) where report maps path -> dict with
    original/compressed byte counts; report["__total__"] aggregates.
    """
    cfg = cfg or ptqtp.PTQTPConfig()
    predicate = predicate or default_predicate
    report: Dict[str, Any] = {}
    tot_before = tot_after = tot_eq13 = 0

    def walk(node, path):
        nonlocal tot_before, tot_after, tot_eq13
        if isinstance(node, dict):
            return {k: walk(v, f"{path}/{k}") for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v, f"{path}/{i}") for i, v in enumerate(node))
        if predicate(path, node, cfg.group_size):
            qk = quantize_kernel(node, cfg)
            before = int(np.prod(node.shape)) * 2  # vs fp16 storage
            # All leading dims (scan stack, MoE experts: (L, E, in, out))
            # multiply the per-matrix bytes; the quantizer stores the matrix
            # transposed, so groups run along d_in = shape[-2]. after_bytes
            # is the exact packed footprint (== QuantizedKernel.nbytes());
            # after_bytes_eq13 is the paper's Eq. 13 with fp16 scales.
            lead = int(np.prod(node.shape[:-2], dtype=np.int64))
            layout = (node.shape[-1], node.shape[-2])  # (d_out, d_in)
            after = lead * ptqtp_weight_bytes(
                layout, cfg.group_size, scale_bytes=qk.alpha.dtype.itemsize)
            after_eq13 = lead * ptqtp_weight_bytes(layout, cfg.group_size)
            report[path] = {"before_bytes": before, "after_bytes": after,
                            "after_bytes_eq13": after_eq13,
                            "shape": tuple(node.shape)}
            tot_before += before
            tot_after += after
            tot_eq13 += after_eq13
            return qk
        return node

    out = walk(params, "")
    report["__total__"] = {
        "before_bytes": tot_before,
        "after_bytes": tot_after,
        "after_bytes_eq13": tot_eq13,
        "compression": (tot_before / tot_after) if tot_after else float("nan"),
        "compression_eq13":
            (tot_before / tot_eq13) if tot_eq13 else float("nan"),
        "n_quantized": len(report),
    }
    return out, report
