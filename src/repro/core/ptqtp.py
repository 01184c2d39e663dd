"""PTQTP: progressive trit-plane approximation with adaptive ridge regression.

Implements the paper's core contribution (Sec. 3, Alg. 1/2):

    W ≈ Ŵ = diag(α¹)·T¹ + diag(α²)·T²,  Tᵏ ∈ {-1,0,1},  α ∈ R²  per group-row.

The weight matrix is reshaped group-wise (G columns per group-row, G=128 by
default, Eq. 6), then alternately optimized:

  * ridge step  — closed-form 2×2 adjugate solve for α (Eq. 1/6/7),
  * adaptive λ  — condition-number-driven regularization growth (Eq. 2-3),
  * trit step   — per-element exhaustive search over the 9 ternary pairs (Eq. 5),

inside a ``lax.while_loop`` with the paper's convergence criterion
``max_i ||α_i,(t) - α_i,(t-1)|| < ε`` and ``t <= T_max``.

Everything is vectorized over group-rows; the whole quantizer is a single
jittable function whose cost is O(T_max · n · d) — the paper's complexity claim.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "PTQTPConfig",
    "ptqtp_quantize",
    "ptqtp_dequantize",
    "ptqtp_error",
    "CANDIDATES",
]

# The 9 ternary candidate pairs (c1, c2) of Eq. 5 / Alg. 2 line 14.
# (0, 0) first so that exact ties (e.g. w == 0) prefer the sparse assignment.
CANDIDATES = np.array(
    [
        [0, 0],
        [0, 1],
        [0, -1],
        [1, 0],
        [-1, 0],
        [1, 1],
        [-1, -1],
        [1, -1],
        [-1, 1],
    ],
    dtype=np.float32,
)


@dataclasses.dataclass(frozen=True)
class PTQTPConfig:
    """Hyper-parameters of the PTQTP quantizer (paper Sec. 4.1 defaults)."""

    group_size: int = 128          # G, Eq. 6
    t_max: int = 50                # max progressive iterations
    eps: float = 1e-4              # convergence tolerance on ||Δα||
    lambda_init: float = 1e-8      # λ₀  (Alg. 2 line 4)
    lambda_max: float = 1.0        # λmax (Eq. 3)
    cond_bound: float = 1e12       # κ threshold (Eq. 3); swept in Table 7
    use_search_kernel: bool = False  # route trit step through the Pallas kernel

    def __post_init__(self):
        assert self.group_size >= 2
        assert self.t_max >= 1


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedTensor:
    """A PTQTP-quantized weight.

    Attributes:
      t1, t2:  int8 trit-planes with values in {-1, 0, 1}, shape = w.shape.
      alpha:   f32/bf16 scaling pairs, shape (n_rows, n_groups, 2) where
               n_groups = d // G and w.shape == (n_rows, d).
      group_size: G.
      iters:   number of progressive iterations actually run (traced scalar).
    """

    t1: jax.Array
    t2: jax.Array
    alpha: jax.Array
    group_size: int
    iters: jax.Array

    @property
    def shape(self):
        return self.t1.shape

    def tree_flatten(self):
        return (self.t1, self.t2, self.alpha, self.iters), (self.group_size,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        t1, t2, alpha, iters = children
        return cls(t1, t2, alpha, aux[0], iters)


def _reshape_groups(w: jax.Array, group_size: int) -> jax.Array:
    """(n, d) -> (n * d // G, G) group-rows (Eq. 6 reshaping)."""
    n, d = w.shape
    if d % group_size != 0:
        raise ValueError(
            f"last dim {d} not divisible by group size {group_size}; "
            "pad the matrix or choose a divisor group size"
        )
    return w.reshape(n * (d // group_size), group_size)


def _ridge_solve(t1, t2, w, lam):
    """Closed-form 2x2 ridge solve per group-row (Eq. 1/6 + adjugate Eq. 7).

    Args:
      t1, t2: (R, G) float32 trit-planes.
      w:      (R, G) float32 weights.
      lam:    (R,)   float32 per-row regularization.
    Returns:
      alpha (R, 2), kappa (R,) condition estimate of the *unregularized-λ* A.
    """
    s11 = jnp.sum(t1 * t1, axis=-1)
    s12 = jnp.sum(t1 * t2, axis=-1)
    s22 = jnp.sum(t2 * t2, axis=-1)
    b1 = jnp.sum(t1 * w, axis=-1)
    b2 = jnp.sum(t2 * w, axis=-1)

    a11 = s11 + lam
    a22 = s22 + lam
    det = a11 * a22 - s12 * s12
    # κ ≈ ||A||_F ||A^{-1}||_F ; for 2x2, ||adj(A)||_F == ||A||_F, so
    # κ = ||A||_F^2 / |det A|  (Eq. 2).
    fro2 = a11 * a11 + a22 * a22 + 2.0 * s12 * s12
    kappa = fro2 / jnp.maximum(jnp.abs(det), 1e-30)

    inv_det = 1.0 / jnp.where(jnp.abs(det) < 1e-30, 1e-30, det)
    alpha1 = (a22 * b1 - s12 * b2) * inv_det
    alpha2 = (-s12 * b1 + a11 * b2) * inv_det
    return jnp.stack([alpha1, alpha2], axis=-1), kappa


def _trit_search(w, alpha):
    """Per-element exhaustive search over the 9 ternary pairs (Eq. 5).

    A compare-select chain over ``CANDIDATES`` in order: the strict ``<``
    keeps the first minimal pair, as an argmin would, while every step
    stays elementwise (an argmin followed by a table gather runs as a
    per-element gather on a TPU and dominated quantization time there).

    Args:
      w: (R, G) float32.
      alpha: (R, 2) float32.
    Returns:
      t1, t2: (R, G) float32 in {-1, 0, 1}.
    """
    a1, a2 = alpha[:, 0:1], alpha[:, 1:2]
    best = jnp.full_like(w, jnp.inf)
    t1 = t2 = jnp.zeros_like(w)
    for c1, c2 in CANDIDATES.tolist():
        err = (w - (a1 * c1 + a2 * c2)) ** 2
        take = err < best
        best = jnp.where(take, err, best)
        t1 = jnp.where(take, c1, t1)
        t2 = jnp.where(take, c2, t2)
    return t1, t2


def _trit_search_kernel(w, alpha):
    """Same as _trit_search but routed through the Pallas ptqtp_search kernel."""
    from repro.kernels.ptqtp_search import ops as search_ops

    return search_ops.ptqtp_search(w, alpha)


@functools.partial(
    jax.jit,
    static_argnames=("group_size", "t_max", "lambda_max", "cond_bound",
                     "use_search_kernel"),
)
def _quantize_grouped(
    wg: jax.Array,
    *,
    group_size: int,
    t_max: int,
    eps: float,
    lambda_init: float,
    lambda_max: float,
    cond_bound: float,
    use_search_kernel: bool,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Run Alg. 1/2 on group-rows wg (R, G). Returns (t1, t2, alpha, iters)."""
    wg = wg.astype(jnp.float32)
    R, G = wg.shape

    # Alg. 2 line 2: sign init with 0 -> 1 replacement.
    sgn = jnp.where(wg >= 0.0, 1.0, -1.0)
    t1 = sgn
    t2 = sgn
    alpha = jnp.ones((R, 2), jnp.float32)  # line 3
    lam = jnp.full((R,), lambda_init, jnp.float32)  # line 4
    eps = jnp.float32(eps)

    search = _trit_search_kernel if use_search_kernel else _trit_search

    def body(state):
        t1, t2, alpha_prev, lam, t, _ = state
        # --- continuous step: adaptive ridge (Alg. 2 lines 6-13) ---
        _, kappa = _ridge_solve(t1, t2, wg, lam)
        lam_new = jnp.where(
            kappa >= cond_bound,
            jnp.minimum(lam * jnp.sqrt(kappa / cond_bound), lambda_max),
            lam,
        )
        alpha, _ = _ridge_solve(t1, t2, wg, lam_new)
        # --- discrete step: 9-candidate exhaustive search (lines 14-21) ---
        t1n, t2n = search(wg, alpha)
        # --- convergence (lines 22-25) ---
        delta = jnp.max(jnp.sqrt(jnp.sum((alpha - alpha_prev) ** 2, axis=-1)))
        converged = delta < eps
        return t1n, t2n, alpha, lam_new, t + 1, converged

    def cond(state):
        *_, t, converged = state
        return jnp.logical_and(t < t_max, jnp.logical_not(converged))

    init = (t1, t2, alpha, lam, jnp.int32(0), jnp.bool_(False))
    t1, t2, alpha, lam, iters, _ = jax.lax.while_loop(cond, body, init)
    # Final α refit against the final trit-planes (keeps ridge/trit consistent).
    alpha, _ = _ridge_solve(t1, t2, wg, lam)
    return t1.astype(jnp.int8), t2.astype(jnp.int8), alpha, iters


def ptqtp_quantize(w: jax.Array, cfg: Optional[PTQTPConfig] = None) -> QuantizedTensor:
    """Quantize a 2-D weight matrix to two trit-planes + group scales.

    Args:
      w:   (n, d) weight matrix (any float dtype).
      cfg: PTQTPConfig (paper defaults if None).

    Returns:
      QuantizedTensor with t1/t2 of shape (n, d) and alpha of shape
      (n, d // G, 2).
    """
    cfg = cfg or PTQTPConfig()
    if w.ndim != 2:
        raise ValueError(f"ptqtp_quantize expects a 2-D matrix, got {w.shape}")
    n, d = w.shape
    wg = _reshape_groups(w, cfg.group_size)
    t1, t2, alpha, iters = _quantize_grouped(
        wg,
        group_size=cfg.group_size,
        t_max=cfg.t_max,
        eps=cfg.eps,
        lambda_init=cfg.lambda_init,
        lambda_max=cfg.lambda_max,
        cond_bound=cfg.cond_bound,
        use_search_kernel=cfg.use_search_kernel,
    )
    n_groups = d // cfg.group_size
    return QuantizedTensor(
        t1=t1.reshape(n, d),
        t2=t2.reshape(n, d),
        alpha=alpha.reshape(n, n_groups, 2),
        group_size=cfg.group_size,
        iters=iters,
    )


def ptqtp_dequantize(q: QuantizedTensor, dtype=jnp.float32) -> jax.Array:
    """Reconstruct Ŵ = diag(α¹)T¹ + diag(α²)T² with group-wise α."""
    n, d = q.t1.shape
    g = q.group_size
    t1 = q.t1.reshape(n, d // g, g).astype(jnp.float32)
    t2 = q.t2.reshape(n, d // g, g).astype(jnp.float32)
    a = q.alpha.astype(jnp.float32)
    w_hat = t1 * a[..., 0:1] + t2 * a[..., 1:2]
    return w_hat.reshape(n, d).astype(dtype)


def ptqtp_error(w: jax.Array, q: QuantizedTensor) -> jax.Array:
    """Relative Frobenius reconstruction error ||W - Ŵ||_F / ||W||_F."""
    w = w.astype(jnp.float32)
    w_hat = ptqtp_dequantize(q)
    return jnp.linalg.norm(w - w_hat) / jnp.maximum(jnp.linalg.norm(w), 1e-30)


def quantize_with_history(w: jax.Array, cfg: Optional[PTQTPConfig] = None):
    """Unrolled variant that records per-iteration Frobenius error.

    Used by tests (monotonicity property) and the Fig. 3 ablation benchmark.
    Returns (QuantizedTensor, errors[t_max+1]) — errors[t] is the error after
    iteration t (errors[0] = after sign init with α=[1,1]).
    """
    cfg = cfg or PTQTPConfig()
    n, d = w.shape
    wg = _reshape_groups(w.astype(jnp.float32), cfg.group_size)

    sgn = jnp.where(wg >= 0.0, 1.0, -1.0)
    t1, t2 = sgn, sgn
    alpha = jnp.ones((wg.shape[0], 2), jnp.float32)
    lam = jnp.full((wg.shape[0],), cfg.lambda_init, jnp.float32)

    def err(t1, t2, alpha):
        w_hat = t1 * alpha[:, 0:1] + t2 * alpha[:, 1:2]
        return jnp.linalg.norm(wg - w_hat)

    errors = [err(t1, t2, alpha)]
    iters_run = 0
    for _ in range(cfg.t_max):
        _, kappa = _ridge_solve(t1, t2, wg, lam)
        lam = jnp.where(
            kappa >= cfg.cond_bound,
            jnp.minimum(lam * jnp.sqrt(kappa / cfg.cond_bound), cfg.lambda_max),
            lam,
        )
        alpha_new, _ = _ridge_solve(t1, t2, wg, lam)
        t1, t2 = _trit_search(wg, alpha_new)
        errors.append(err(t1, t2, alpha_new))
        delta = jnp.max(jnp.sqrt(jnp.sum((alpha_new - alpha) ** 2, axis=-1)))
        alpha = alpha_new
        iters_run += 1
        if bool(delta < cfg.eps):
            break
    q = QuantizedTensor(
        t1=t1.astype(jnp.int8).reshape(n, d),
        t2=t2.astype(jnp.int8).reshape(n, d),
        alpha=alpha.reshape(n, d // cfg.group_size, 2),
        group_size=cfg.group_size,
        iters=jnp.int32(iters_run),
    )
    return q, jnp.stack(errors)
