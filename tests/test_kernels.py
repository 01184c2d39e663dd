"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles.

Kernels run in interpret mode (CPU container; TPU is the lowering target).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.packing import pack_trits
from repro.core.ptqtp import PTQTPConfig, ptqtp_quantize
from repro.kernels.ptqtp_search import ops as search_ops
from repro.kernels.ptqtp_search import ref as search_ref
from repro.kernels.ternary_matmul import ops as tm_ops
from repro.kernels.ternary_matmul import ref as tm_ref


def _quantized(n_out, d_in, seed=0, g=128):
    w = jnp.asarray(np.random.default_rng(seed)
                    .standard_normal((n_out, d_in), dtype=np.float32))
    q = ptqtp_quantize(w, PTQTPConfig(group_size=g, t_max=5))
    return q, pack_trits(q.t1), pack_trits(q.t2)


class TestTernaryMatmul:
    @pytest.mark.parametrize("b,d_in,d_out", [
        (1, 128, 128),      # minimal tile
        (4, 256, 512),      # multi-group
        (3, 384, 256),      # non-pow2 batch/contraction
        (16, 512, 384),     # wider
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_oracle(self, b, d_in, d_out, dtype):
        q, t1p, t2p = _quantized(d_out, d_in)
        x = jnp.asarray(np.random.default_rng(1)
                        .standard_normal((b, d_in), dtype=np.float32)
                        ).astype(dtype)
        y_k = tm_ops.ternary_matmul(x, t1p, t2p, q.alpha, group_size=128,
                                    backend="pallas")
        y_r = tm_ref.ternary_matmul_ref(x.astype(jnp.float32), q.t1, q.t2,
                                        q.alpha, group_size=128)
        tol = 1e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(y_k, np.float32),
                                   np.asarray(y_r), rtol=tol, atol=tol * 10)

    @pytest.mark.parametrize("backend", ["grouped", "pallas", "ref"])
    def test_backends_agree(self, backend):
        q, t1p, t2p = _quantized(256, 384, seed=2)
        x = jnp.asarray(np.random.default_rng(3)
                        .standard_normal((5, 384), dtype=np.float32))
        y = tm_ops.ternary_matmul(x, t1p, t2p, q.alpha, group_size=128,
                                  backend=backend)
        y_r = tm_ref.ternary_matmul_ref(x, q.t1, q.t2, q.alpha, group_size=128)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_r),
                                   rtol=1e-4, atol=1e-4)

    def test_batched_leading_dims(self):
        """(B, S, d_in) activations — the in-model call shape."""
        q, t1p, t2p = _quantized(128, 256, seed=4)
        x = jnp.asarray(np.random.default_rng(5)
                        .standard_normal((2, 7, 256), dtype=np.float32))
        y = tm_ops.ternary_matmul(x, t1p, t2p, q.alpha, group_size=128)
        assert y.shape == (2, 7, 128)
        y_r = tm_ref.ternary_matmul_ref(x.reshape(-1, 256), q.t1, q.t2,
                                        q.alpha, group_size=128)
        np.testing.assert_allclose(np.asarray(y).reshape(-1, 128),
                                   np.asarray(y_r), rtol=1e-4, atol=1e-4)

    def test_equals_dense_matmul_of_dequantized(self):
        """y == x @ Ŵᵀ where Ŵ is the dequantized matrix (end-to-end
        semantics of the multiplication-free path)."""
        from repro.core.ptqtp import ptqtp_dequantize

        q, t1p, t2p = _quantized(128, 256, seed=6)
        x = jnp.asarray(np.random.default_rng(7)
                        .standard_normal((3, 256), dtype=np.float32))
        y = tm_ops.ternary_matmul(x, t1p, t2p, q.alpha, group_size=128)
        w_hat = ptqtp_dequantize(q)  # (n_out, d_in)
        np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w_hat.T),
                                   rtol=1e-4, atol=1e-4)


class TestSmallMFastPath:
    """Decode fast path: the small-m fused kernel vs the ref oracle."""

    @pytest.mark.parametrize("m", [1, 3, 5])
    @pytest.mark.parametrize("n,d", [
        (128, 256),     # aligned n
        (96, 256),      # n < 128, not divisible by 128
        (192, 128),     # n > 128, not divisible by 128 (bn = 96)
    ])
    def test_small_m_parity(self, m, n, d):
        q, t1p, t2p = _quantized(n, d, seed=m)
        x = jnp.asarray(np.random.default_rng(m + 10)
                        .standard_normal((m, d), dtype=np.float32))
        y = tm_ops.ternary_matmul(x, t1p, t2p, q.alpha, group_size=128,
                                  backend="pallas")
        y_r = tm_ops.ternary_matmul(x, t1p, t2p, q.alpha, group_size=128,
                                    backend="ref")
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_r),
                                   rtol=1e-4, atol=1e-4)

    def test_small_m_kernel_direct(self):
        """The kernel entry point itself with the whole decode batch in one
        m block (block_m = m), bypassing dispatch."""
        from repro.kernels.ternary_matmul.kernel import ternary_matmul_pallas

        q, t1p, t2p = _quantized(256, 384, seed=21)
        x = jnp.asarray(np.random.default_rng(22)
                        .standard_normal((4, 384), dtype=np.float32))
        y = ternary_matmul_pallas(x, t1p, t2p, q.alpha, group_size=128,
                                  block_m=4, block_n=128, interpret=True)
        y_r = tm_ref.ternary_matmul_ref(x, q.t1, q.t2, q.alpha, group_size=128)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_r),
                                   rtol=1e-4, atol=1e-4)

    def test_bf16_activation(self):
        q, t1p, t2p = _quantized(128, 256, seed=31)
        x = jnp.asarray(np.random.default_rng(32)
                        .standard_normal((2, 256), dtype=np.float32)
                        ).astype(jnp.bfloat16)
        y = tm_ops.ternary_matmul(x, t1p, t2p, q.alpha, group_size=128,
                                  backend="pallas")
        y_r = tm_ref.ternary_matmul_ref(x.astype(jnp.float32), q.t1, q.t2,
                                        q.alpha, group_size=128)
        np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(y_r),
                                   rtol=2e-2, atol=2e-1)


class TestBackendSelection:
    def test_auto_resolves_per_platform(self):
        # this suite runs on CPU: auto must pick the XLA grouped path
        assert tm_ops.resolve_backend("auto") == "grouped"
        assert tm_ops.resolve_backend(None) == "grouped"
        assert tm_ops.resolve_backend("auto", platform="tpu") == "pallas"
        assert tm_ops.resolve_backend("ref") == "ref"

    def test_auto_backend_matches_ref(self):
        q, t1p, t2p = _quantized(128, 256, seed=41)
        x = jnp.asarray(np.random.default_rng(42)
                        .standard_normal((3, 256), dtype=np.float32))
        y = tm_ops.ternary_matmul(x, t1p, t2p, q.alpha, group_size=128,
                                  backend="auto")
        y_r = tm_ref.ternary_matmul_ref(x, q.t1, q.t2, q.alpha, group_size=128)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_r),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("n,cap,want", [
        (128, 128, 128), (256, 128, 128), (96, 128, 96), (192, 128, 96),
        (384, 128, 128), (259, 128, 37), (127, 128, 127), (97, 32, 1),
        (5504, 128, 128),
    ])
    def test_largest_divisor(self, n, cap, want):
        got = tm_ops._largest_divisor_at_most(n, cap)
        assert got == want
        assert n % got == 0 and got <= cap

    def test_unpacked_planes_dispatch(self):
        """int8 (pre-unpacked) planes: 'auto' adapts to grouped; an explicit
        ask for another backend fails loudly instead of being overridden."""
        from repro.core.packing import unpack_trits

        q, t1p, t2p = _quantized(128, 256, seed=51)
        t1, t2 = unpack_trits(t1p), unpack_trits(t2p)
        x = jnp.asarray(np.random.default_rng(52)
                        .standard_normal((2, 256), dtype=np.float32))
        y = tm_ops.ternary_matmul(x, t1, t2, q.alpha, group_size=128,
                                  backend="auto")
        y_r = tm_ref.ternary_matmul_ref(x, q.t1, q.t2, q.alpha, group_size=128)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_r),
                                   rtol=1e-4, atol=1e-4)
        with pytest.raises(ValueError, match="packed uint8"):
            tm_ops.ternary_matmul(x, t1, t2, q.alpha, group_size=128,
                                  backend="pallas")

    def test_tile_selection_cached(self):
        tm_ops._select_tiles(7, 4096)
        hits_before = tm_ops._select_tiles.cache_info().hits
        tm_ops._select_tiles(7, 4096)  # identical call must hit the cache
        assert tm_ops._select_tiles.cache_info().hits == hits_before + 1
        assert tm_ops._select_tiles(7, 4096) == (7, 256)
        assert tm_ops._select_tiles(256, 384) == (128, 128)
        assert tm_ops._select_tiles(8, 151936) == (8, 128)  # 151936 = 1187·128
        assert tm_ops._select_tiles(3, 96) == (3, 128)  # padded to 128
        assert tm_ops._select_tiles(8, 32064) == (8, 128)  # 250.5·128


class TestPTQTPSearchKernel:
    @pytest.mark.parametrize("r,g", [(8, 128), (32, 128), (128, 128),
                                     (16, 256)])
    def test_matches_oracle(self, r, g):
        rng = np.random.default_rng(r)
        w = jnp.asarray(rng.standard_normal((r, g), dtype=np.float32))
        alpha = jnp.asarray(rng.standard_normal((r, 2), dtype=np.float32))
        t1k, t2k = search_ops.ptqtp_search(w, alpha)
        t1r, t2r = search_ref.ptqtp_search_ref(w, alpha)
        np.testing.assert_array_equal(np.asarray(t1k), np.asarray(t1r))
        np.testing.assert_array_equal(np.asarray(t2k), np.asarray(t2r))

    def test_selection_is_optimal(self):
        """Every selected pair achieves the elementwise minimum error."""
        rng = np.random.default_rng(9)
        w = jnp.asarray(rng.standard_normal((16, 128), dtype=np.float32))
        alpha = jnp.asarray(rng.standard_normal((16, 2), dtype=np.float32))
        t1, t2 = search_ops.ptqtp_search(w, alpha)
        chosen = (np.asarray(alpha)[:, :1] * np.asarray(t1)
                  + np.asarray(alpha)[:, 1:] * np.asarray(t2))
        err_chosen = (np.asarray(w) - chosen) ** 2
        cand = search_ref.CANDIDATES
        vals = np.asarray(alpha) @ cand.T  # (R, 9)
        err_best = ((np.asarray(w)[:, :, None] - vals[:, None, :]) ** 2
                    ).min(-1)
        np.testing.assert_allclose(err_chosen, err_best, rtol=1e-5,
                                   atol=1e-6)

    def test_quantizer_kernel_route_agrees(self):
        """PTQTPConfig(use_search_kernel=True) — full quantizer through the
        Pallas kernel matches the jnp route."""
        from repro.core.ptqtp import ptqtp_error

        w = jnp.asarray(np.random.default_rng(11)
                        .standard_normal((8, 256), dtype=np.float32))
        q_j = ptqtp_quantize(w, PTQTPConfig(t_max=10))
        q_k = ptqtp_quantize(w, PTQTPConfig(t_max=10, use_search_kernel=True))
        np.testing.assert_array_equal(np.asarray(q_j.t1), np.asarray(q_k.t1))
        np.testing.assert_allclose(np.asarray(q_j.alpha),
                                   np.asarray(q_k.alpha), rtol=1e-5)
        assert abs(float(ptqtp_error(w, q_j)) -
                   float(ptqtp_error(w, q_k))) < 1e-6
