"""Weights made from the seed: random trit-planes in the artifact's layout.

Every leaf is a pure function of (seed, leaf path, layer, and in a stack
of matrices such as a layer's experts the matrix's place), so the program
side and the plain reference draw the same numbers without sharing any
array. Every value is an integer times a power of two, exact in its dtype,
so no rounding inside either side's programs can make the two differ.

* A leaf that the program's quantizer would quantize (its own
  ``default_predicate``: a 2-D kernel, or a stack of them, whose
  contraction divides the group size; embeddings, norms, routers, decays
  and LoRAs excepted) becomes two trit-planes T¹, T² uniform over {-1, 0, 1} and f32 scales α¹, α² per
  group of ``group`` inputs, drawn around sqrt(3 / (4 d_in)) so that
  Ŵ = α¹T¹ + α²T² has about the 1/sqrt(d_in) scale of the program's own
  initializer. The program gets them packed (``QuantizedKernel``); the
  reference dequantizes them itself, one matrix at a time.
* Every other leaf is drawn in its own dtype from a rule chosen by its
  name: the rules every architecture shares (``SHARED_RULES``) and the
  architecture module's own (``LEAF_RULES``); an unknown name is an
  error, not a default.

The program side is one jitted call, which maps over the layers of each
stacked leaf so that no stack of int8 trits or f32 values is ever whole.
"""

from __future__ import annotations

import dataclasses
import math
import zlib
from typing import Any, Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

def seed_key(seed: int) -> jax.Array:
    """A raw uint32[2] PRNG key for any seed in [0, 2**64)."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return jnp.asarray([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                       jnp.uint32)


def leaf_key(key: jax.Array, path: str) -> jax.Array:
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def ints(key, shape, lo: int, hi: int):
    """Integers uniform over [lo, hi] (hi - lo < 255), as int32."""
    b = jax.random.bits(key, shape, jnp.uint8).astype(jnp.int32)
    return lo + b % (hi - lo + 1)


def trits(key, n: int, d: int) -> jax.Array:
    """(n, d) int8 trits in {-1, 0, 1}."""
    b = jax.random.bits(key, (n, d), jnp.uint8)
    return (b % 3).astype(jnp.int8) - 1


def alphas(key, n: int, d: int, group: int) -> jax.Array:
    """(n, d // group, 2) f32 scales: k · 2⁻¹⁶, k uniform over [c/2, 3c/2]
    with c · 2⁻¹⁶ ≈ sqrt(3 / (4 d))."""
    c = int(round(math.sqrt(3.0 / (4.0 * d)) * 2 ** 16))
    b = jax.random.bits(key, (n, d // group, 2), jnp.uint16).astype(jnp.int32)
    k = c // 2 + b % (c + 1)
    return k.astype(jnp.float32) * (2.0 ** -16)


def ternary_parts(key, d_in: int, d_out: int, group: int):
    """(t1, t2, alpha) of one matrix in the quantizer's output-major
    layout: t (d_out, d_in) int8, alpha (d_out, d_in // group, 2) f32."""
    return (trits(jax.random.fold_in(key, 1), d_out, d_in),
            trits(jax.random.fold_in(key, 2), d_out, d_in),
            alphas(jax.random.fold_in(key, 3), d_out, d_in, group))


def dequantized(t1, t2, alpha, group: int) -> jax.Array:
    """Ŵ (d_in, d_out) in f32 from one matrix's parts."""
    n, d = t1.shape
    a = alpha.astype(jnp.float32)
    w = (t1.reshape(n, d // group, group).astype(jnp.float32) * a[..., 0:1]
         + t2.reshape(n, d // group, group).astype(jnp.float32) * a[..., 1:2])
    return w.reshape(n, d).T


def signed(key, shape, exp: int):
    """Integers uniform over [-127, 127] times 2**exp, as f32."""
    return ints(key, shape, -127, 127).astype(jnp.float32) * 2.0 ** exp


# name -> rule(key, shape, path) giving f32 values exact in bf16: the leaves
# every architecture has; an architecture's own are its ``LEAF_RULES``
SHARED_RULES = {
    "scale": lambda k, s, p: 1.0 + ints(k, s, -16, 16) * 2.0 ** -7,
    "embedding": lambda k, s, p: signed(k, s, -12),
    "bias": lambda k, s, p: signed(k, s, -9),
}


def leaf_rules(arch) -> Dict[str, Callable]:
    """The shared rules with the architecture module's ``LEAF_RULES``
    merged over them; a name that both give is an error."""
    own = getattr(arch, "LEAF_RULES", {})
    clash = sorted(SHARED_RULES.keys() & own.keys())
    if clash:
        raise ValueError(f"{arch.__name__} redefines shared leaf rules "
                         f"{clash}")
    return {**SHARED_RULES, **own}


def _rule(path: str, rules: Dict[str, Callable]) -> Callable:
    name = path.rsplit("/", 1)[-1]
    if name not in rules:
        raise KeyError(f"no rule for leaf {path!r}: add one to the "
                       f"architecture's LEAF_RULES")
    return rules[name]


def dense_value(key, path: str, shape, dtype, rules) -> jax.Array:
    return _rule(path, rules)(key, tuple(shape), path).astype(dtype)


def stacked(path: str) -> bool:
    """Leaves under /blocks/ carry a leading layer (scan period) axis."""
    return "/blocks/" in path


def flatten(tree, path: str = "") -> List[Tuple[str, Any]]:
    """(path, leaf) pairs of a nested-dict tree, paths as the program's
    quantizer spells them (``/blocks/b0/attn/wq/kernel``)."""
    if isinstance(tree, dict):
        out = []
        for k in tree:
            out += flatten(tree[k], f"{path}/{k}")
        return out
    return [(path, tree)]


def _unflatten(pairs: List[Tuple[str, Any]]) -> Dict[str, Any]:
    root: Dict[str, Any] = {}
    for path, leaf in pairs:
        node = root
        parts = path.strip("/").split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return root


def leaves(model_cfg, group: int) -> List[Tuple[str, Any, bool]]:
    """(path, shape and dtype, quantized?) of every leaf of the program's
    params; the program's own quantizer decides which are quantized."""
    from repro.core.quantize_model import default_predicate
    from repro.models import init_params

    shapes = jax.eval_shape(lambda: init_params(model_cfg,
                                                jax.random.PRNGKey(0)))
    return [(path, sds, default_predicate(
                path, np.broadcast_to(np.int8(0), sds.shape), group))
            for path, sds in flatten(shapes)]


def program_params(model_cfg, seed: int, group: int, rules):
    """The program's params for ``model_cfg``, made on the device in one
    jitted call: ternary leaves as packed ``QuantizedKernel``s, the others
    by ``rules`` (``leaf_rules``)."""
    from repro.core.packing import pack_trits
    from repro.core.quantize_model import QuantizedKernel

    tree = leaves(model_cfg, group)

    def packed(key, d_in, d_out):
        t1, t2, a = ternary_parts(key, d_in, d_out, group)
        return pack_trits(t1), pack_trits(t2), a

    def build(raw_key):
        out = []
        for path, sds, ternary in tree:
            k = leaf_key(raw_key, path)
            shape = tuple(sds.shape)
            per = shape[1:] if stacked(path) else shape
            if ternary:
                *batch, d_in, d_out = per
                fn = lambda kk, d_in=d_in, d_out=d_out: packed(kk, d_in, d_out)
                if batch:
                    fn = _each(fn, batch)
            else:
                fn = (lambda kk, path=path, per=per, dt=sds.dtype:
                      dense_value(kk, path, per, dt, rules))
            if stacked(path):
                val = jax.lax.map(
                    lambda l, k=k, fn=fn: fn(jax.random.fold_in(k, l)),
                    jnp.arange(shape[0]))
            else:
                val = fn(k)
            if ternary:
                val = QuantizedKernel(*val, d_in, d_out, group)
            out.append((path, val))
        return _unflatten(out)

    return jax.jit(build)(seed_key(seed))


def _each(fn, batch):
    """``fn`` over a stack of ``batch`` matrices (the experts of a layer):
    the i-th (row-major) from ``fold_in(key, i)``."""
    n = math.prod(batch)

    def stack(key):
        out = jax.lax.map(lambda i: fn(jax.random.fold_in(key, i)),
                          jnp.arange(n))
        return jax.tree.map(lambda a: a.reshape(*batch, *a.shape[1:]), out)

    return stack


def _ref_matrix_impl(raw_key, layer, index, *, path, d_in, d_out, group,
                     stack, in_stack):
    k = leaf_key(raw_key, path)
    if stack:
        k = jax.random.fold_in(k, layer)
    if in_stack:
        k = jax.random.fold_in(k, index)
    return dequantized(*ternary_parts(k, d_in, d_out, group), group)


_ref_matrix_jitted = jax.jit(_ref_matrix_impl, static_argnames=(
    "path", "d_in", "d_out", "group", "stack", "in_stack"))


def _ref_dense_impl(raw_key, layer, *, path, shape, dtype, stack, rule):
    k = leaf_key(raw_key, path)
    if stack:
        k = jax.random.fold_in(k, layer)
    return rule(k, shape, path).astype(dtype).astype(jnp.float32)


_ref_dense_jitted = jax.jit(_ref_dense_impl, static_argnames=(
    "path", "shape", "dtype", "stack", "rule"))


@dataclasses.dataclass(frozen=True)
class Seeded:
    """The weights one seed draws under one architecture's leaf rules
    (``leaf_rules``), as the plain reference reads them: one matrix or
    leaf, or one layer of a stacked one, at a time, in f32."""
    seed: int
    rules: Dict[str, Callable]

    def matrix(self, path: str, layer: int, d_in: int, d_out: int,
               group: int, index: int = -1) -> jax.Array:
        """Ŵ (d_in, d_out) f32 of one ternary matrix (layer ``layer`` of a
        stacked leaf, or -1 for an unstacked one; ``index``: its place,
        row-major, in a stack of matrices such as a layer's experts, or
        -1), drawn as the program's."""
        return _ref_matrix_jitted(seed_key(self.seed), max(layer, 0),
                                  max(index, 0), path=path, d_in=d_in,
                                  d_out=d_out, group=group, stack=layer >= 0,
                                  in_stack=index >= 0)

    def leaf(self, path: str, layer: int, shape, dtype) -> jax.Array:
        """A non-ternary leaf in f32 (its values are exact in ``dtype``)."""
        return _ref_dense_jitted(seed_key(self.seed), max(layer, 0),
                                 path=path, shape=tuple(shape),
                                 dtype=jnp.dtype(dtype), stack=layer >= 0,
                                 rule=_rule(path, self.rules))
