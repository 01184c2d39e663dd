"""Jitted wrapper for the fused trit-search kernel: compiled on TPU, run by
the Pallas interpreter elsewhere (the platform alone decides)."""

from __future__ import annotations

import jax

from repro.kernels.ptqtp_search.kernel import ptqtp_search_pallas


def ptqtp_search(w: jax.Array, alpha: jax.Array):
    """(t1, t2) f32 planes for group-rows w (R, G) and scales alpha (R, 2)."""
    return ptqtp_search_pallas(w, alpha,
                               interpret=jax.default_backend() != "tpu")
