"""Per-architecture smoke tests + prefill/decode consistency.

Every assigned arch instantiates its reduced config, runs one forward/train
step on CPU, and asserts output shapes + finiteness (the (f) deliverable).
Cache correctness: last-token logits must agree between the full forward,
prefill, and prefill-then-decode paths.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import (decode_step, forward, init_decode_state,
                          init_params, loss_fn, prefill)
from repro.optim.adamw import AdamW
from repro.training.train_step import init_train_state, make_train_step

ARCHS = list(configs.ARCH_IDS)


def _batch(cfg, b=2, s=16, seed=0):
    rng = np.random.default_rng(seed)
    if cfg.embed_inputs:
        toks = rng.integers(0, min(cfg.vocab_size, 256), (b, s))
        batch = {"tokens": jnp.asarray(toks, jnp.int32)}
    else:
        batch = {"embeddings": jnp.asarray(
            rng.standard_normal((b, s, cfg.d_model), dtype=np.float32))}
    batch["labels"] = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
class TestArchSmoke:
    def test_forward_shapes_and_finite(self, arch):
        cfg = configs.get_smoke_config(arch)
        params = init_params(cfg, jax.random.PRNGKey(0))
        batch = _batch(cfg)
        logits = forward(params, cfg, batch)
        assert logits.shape == (2, 16, cfg.vocab_size)
        assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))

    def test_one_train_step(self, arch):
        cfg = configs.get_smoke_config(arch)
        params = init_params(cfg, jax.random.PRNGKey(0))
        opt = AdamW(lr=1e-3)
        state = init_train_state(cfg, params, opt)
        step = jax.jit(make_train_step(cfg, opt))
        batch = _batch(cfg)
        new_state, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))
        assert int(new_state["step"]) == 1
        # params actually moved
        moved = jax.tree.map(
            lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                               - b.astype(jnp.float32)))),
            state["params"], new_state["params"])
        assert max(jax.tree.leaves(moved)) > 0

    def test_prefill_decode_consistency(self, arch):
        """forward(x)[:, -1] == prefill(x) logits; and prefill(x[:, :-1])
        then decode(x[:, -1]) matches too — the cache-correctness oracle."""
        cfg = configs.get_smoke_config(arch)
        params = init_params(cfg, jax.random.PRNGKey(1))
        batch = _batch(cfg, b=2, s=8, seed=1)
        del batch["labels"]
        full = forward(params, cfg, batch)
        last_full = np.asarray(full[:, -1], np.float32)

        lg_pre, _ = prefill(params, cfg, batch, capacity=16)
        np.testing.assert_allclose(np.asarray(lg_pre, np.float32), last_full,
                                   rtol=2e-2, atol=2e-2)

        if cfg.embed_inputs:
            head = {"tokens": batch["tokens"][:, :-1]}
            tail = batch["tokens"][:, -1]
        else:
            head = {"embeddings": batch["embeddings"][:, :-1]}
            tail = batch["embeddings"][:, -1]
        _, state = prefill(params, cfg, head, capacity=16)
        lg_dec, state2 = decode_step(params, cfg, state, tail)
        np.testing.assert_allclose(np.asarray(lg_dec, np.float32), last_full,
                                   rtol=2e-2, atol=2e-2)
        assert int(state2["pos"][0]) == 8

    def test_decode_state_structure(self, arch):
        cfg = configs.get_smoke_config(arch)
        st = init_decode_state(cfg, batch=2, capacity=16)
        assert st["pos"].shape == (2,)
        spec = jax.eval_shape(lambda: init_decode_state(cfg, 2, 16))
        same = jax.tree.map(lambda a, b: a.shape == b.shape and
                            a.dtype == b.dtype, st, spec)
        assert all(jax.tree.leaves(same))


def test_param_counts_match_instantiated():
    """Analytic param_counts() (roofline MODEL_FLOPS source) must track the
    real parameter tree within the bias/norm margin — checked on the FULL
    configs via eval_shape (no allocation)."""
    for arch in ("qwen2-1.5b", "deepseek-moe-16b", "deepseek-v2-lite",
                 "rwkv6-3b", "gemma3-27b", "llama3-405b"):
        cfg = configs.get_config(arch)
        shapes = jax.eval_shape(lambda c=cfg: init_params(
            c, jax.random.PRNGKey(0)))
        real = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
        analytic, _ = cfg.param_counts()
        assert abs(real - analytic) / real < 0.05, (arch, real, analytic)


def test_long_context_flags():
    """long_500k runs only for sub-quadratic archs (DESIGN.md §4)."""
    expected_long = {"rwkv6-3b", "recurrentgemma-2b", "gemma3-27b"}
    actual = {a for a in ARCHS
              if configs.get_config(a).supports_long_context}
    assert actual == expected_long
    cells = configs.runnable_cells()
    assert len(cells) == 36  # 44 - 8 documented skips


class TestInt8KVCache:
    """§Perf iteration 5: int8 KV cache correctness (beyond-paper feature)."""

    @pytest.mark.parametrize("arch", ["qwen2-1.5b", "gemma3-27b",
                                      "recurrentgemma-2b"])
    def test_prefill_decode_consistency_int8(self, arch):
        cfg = configs.get_smoke_config(arch).scaled(kv_cache_dtype="int8")
        params = init_params(cfg, jax.random.PRNGKey(1))
        batch = _batch(cfg, b=2, s=8, seed=1)
        del batch["labels"]
        full = forward(params, cfg, batch)
        last_full = np.asarray(full[:, -1], np.float32)
        head = {"tokens": batch["tokens"][:, :-1]}
        _, state = prefill(params, cfg, head, capacity=16)
        lg, _ = decode_step(params, cfg, state, batch["tokens"][:, -1])
        # int8 cache: slightly looser tolerance than bf16
        np.testing.assert_allclose(np.asarray(lg, np.float32), last_full,
                                   rtol=8e-2, atol=8e-2)

    def test_cache_is_actually_int8(self):
        from repro.models import init_decode_state

        cfg = configs.get_smoke_config("qwen2-1.5b").scaled(
            kv_cache_dtype="int8")
        st = init_decode_state(cfg, batch=2, capacity=16)
        k = st["blocks"]["b0"]["k"]
        assert k.dtype == jnp.int8
        assert "k_scale" in st["blocks"]["b0"]

    def test_int8_cache_halves_bytes(self):
        from repro.models import init_decode_state

        def nbytes(cfg):
            st = init_decode_state(cfg, batch=2, capacity=64)
            return sum(x.size * x.dtype.itemsize
                       for x in jax.tree.leaves(st["blocks"])
                       if x.dtype != jnp.int32)

        base = configs.get_smoke_config("qwen2-1.5b").scaled(
            param_dtype="bfloat16", activation_dtype="bfloat16")
        b16 = nbytes(base)
        i8 = nbytes(base.scaled(kv_cache_dtype="int8"))
        assert i8 < 0.6 * b16, (i8, b16)
