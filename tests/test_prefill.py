"""Bucketed/chunked prefill: model-level exactness + scheduler edge cases."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import (decode_step, init_decode_state, init_params,
                          prefill, prefill_chunk)
from repro.serving import SamplingParams
from repro.serving.engine import (EngineConfig, SerialAdmitEngine,
                                  ServingEngine)

ARCHS = ("qwen2-1.5b", "rwkv6-3b", "recurrentgemma-2b")


@pytest.fixture(scope="module")
def models():
    out = {}
    for arch in ARCHS:
        cfg = configs.get_smoke_config(arch)
        out[arch] = (cfg, init_params(cfg, jax.random.PRNGKey(0)))
    return out


def _greedy(params, cfg, state, tok, n):
    toks = []
    for _ in range(n):
        logits, state = decode_step(params, cfg, state, tok)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        toks.append(int(tok[0]))
    return toks


class TestPrefillChunkModel:
    @pytest.mark.parametrize("arch", ARCHS)
    def test_chunked_matches_full(self, models, arch):
        """Prompt fed in chunks (with padding in the tail chunk) must yield
        the same last-token logits and a decode-equivalent state as one
        whole-prompt prefill — incl. sliding-window archs whose ring wraps."""
        cfg, params = models[arch]
        rng = np.random.default_rng(0)
        prompt = rng.integers(1, 400, size=11).tolist()
        cap = 16  # < prompt for window layers of recurrentgemma (window 8)
        lg_full, st_full = prefill(
            params, cfg, {"tokens": jnp.asarray([prompt], jnp.int32)},
            capacity=cap)
        st = init_decode_state(cfg, 1, cap)
        for start in range(0, len(prompt), 4):
            chunk = prompt[start:start + 4]
            t = np.zeros((1, 4), np.int32)
            t[0, :len(chunk)] = chunk
            lg, st = prefill_chunk(params, cfg, st, {"tokens": jnp.asarray(t)},
                                   jnp.asarray([len(chunk)], jnp.int32))
        np.testing.assert_allclose(np.asarray(lg, np.float32),
                                   np.asarray(lg_full, np.float32),
                                   rtol=2e-4, atol=2e-4)
        tok = jnp.argmax(lg_full, -1).astype(jnp.int32)
        assert _greedy(params, cfg, st_full, tok, 4) == \
            _greedy(params, cfg, st, tok, 4)

    @pytest.mark.parametrize("arch", ARCHS)
    def test_padded_batch_matches_per_row(self, models, arch):
        """Rows of different lengths in one padded bucket must each match
        their own solo prefill (padding never leaks across rows)."""
        cfg, params = models[arch]
        prompts = [[5, 9], [1, 2, 3, 4, 7], [11, 3, 6]]
        cap, L = 16, 8
        st = init_decode_state(cfg, len(prompts), cap)
        toks = np.zeros((len(prompts), L), np.int32)
        lens = np.asarray([len(p) for p in prompts], np.int32)
        for i, p in enumerate(prompts):
            toks[i, :len(p)] = p
        lg, st = prefill_chunk(params, cfg, st, {"tokens": jnp.asarray(toks)},
                               jnp.asarray(lens))
        assert np.asarray(st["pos"]).tolist() == lens.tolist()
        for i, p in enumerate(prompts):
            lg1, _ = prefill(params, cfg,
                             {"tokens": jnp.asarray([p], jnp.int32)},
                             capacity=cap)
            np.testing.assert_allclose(np.asarray(lg[i], np.float32),
                                       np.asarray(lg1[0], np.float32),
                                       rtol=2e-4, atol=2e-4)

    def test_zero_length_rows_are_noops(self, models):
        """lengths == 0 must leave every state leaf bit-identical — that is
        what lets decoding/free slots ride through the prefill dispatch."""
        cfg, params = models["qwen2-1.5b"]
        st = init_decode_state(cfg, 2, 16)
        toks = jnp.asarray([[3, 4, 5, 0], [7, 8, 0, 0]], jnp.int32)
        _, st = prefill_chunk(params, cfg, st, {"tokens": toks},
                              jnp.asarray([3, 2], jnp.int32))
        _, st2 = prefill_chunk(params, cfg, st, {"tokens": toks},
                               jnp.zeros((2,), jnp.int32))
        for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(st2)):
            assert jnp.array_equal(a, b)


def _ring_rows(state, row):
    """Every ring leaf of a state at one batch row, all layers at once:
    {path: array}. Recurrent leaves and the top-level position are left
    out; ``row_axis`` says where each leaf keeps its rows."""
    from repro.models.attention import row_axis

    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        keys = [k.key for k in path]
        if keys[-1] not in ("k", "v", "k_scale", "v_scale", "pos") \
                or len(keys) == 1:
            continue
        axis = row_axis(keys[-1]) + (keys[0] == "blocks")
        out["/".join(keys)] = np.asarray(jnp.take(leaf, row, axis=axis))
    return out


class TestStackedRing:
    """The ring of a scan's layers is one stack carried through the layer
    scan: each layer is read at its index and written only where its
    tokens land."""

    @pytest.fixture(scope="class")
    def deep(self, models):
        cfg, _ = models["qwen2-1.5b"]
        cfg = cfg.scaled(n_layers=4)
        return cfg, init_params(cfg, jax.random.PRNGKey(1))

    @pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
    def test_length_zero_row_leaves_every_layer_untouched(self, deep,
                                                          kv_dtype):
        """A row that rides through a prefill chunk with length 0, or
        through a decode step inactive, keeps its ring slice in every
        layer bit for bit, while the other row's slice changes in every
        layer."""
        cfg, params = deep
        cfg = cfg.scaled(kv_cache_dtype=kv_dtype)
        st = init_decode_state(cfg, 2, 16)
        toks = jnp.asarray([[3, 4, 5, 6], [7, 8, 9, 1]], jnp.int32)
        _, st = prefill_chunk(params, cfg, st, {"tokens": toks},
                              jnp.asarray([4, 2], jnp.int32))
        before = [_ring_rows(st, r) for r in (0, 1)]
        _, st2 = prefill_chunk(params, cfg, st, {"tokens": toks},
                               jnp.asarray([3, 0], jnp.int32))
        _, st3 = decode_step(params, cfg, st2, jnp.asarray([5, 5], jnp.int32),
                             active=jnp.asarray([True, False]))
        for after in (st2, st3):
            kept, moved = _ring_rows(after, 1), _ring_rows(after, 0)
            assert kept.keys() == before[1].keys() and len(kept) >= 3
            for path, leaf in kept.items():
                np.testing.assert_array_equal(leaf, before[1][path], path)
            for path in ("blocks/b0/k", "blocks/b0/pos"):
                for layer in range(cfg.n_layers):
                    assert not np.array_equal(moved[path][layer],
                                              before[0][path][layer])

    def test_layers_hold_their_own_keys(self, deep):
        """Each layer's slice of the stacked ring holds that layer's keys:
        the stack after a chunk equals the keys each layer computes from
        its own input, which a whole-prompt prefill writes layer by
        layer."""
        cfg, params = deep
        prompt = jnp.asarray([[3, 4, 5, 6, 7]], jnp.int32)
        _, st_full = prefill(params, cfg, {"tokens": prompt}, capacity=8)
        st = init_decode_state(cfg, 1, 8)
        _, st = prefill_chunk(params, cfg, st, {"tokens": prompt},
                              jnp.asarray([5], jnp.int32))
        ring, ring_full = st["blocks"]["b0"], st_full["blocks"]["b0"]
        np.testing.assert_array_equal(ring["pos"], ring_full["pos"])
        np.testing.assert_allclose(ring["k"], ring_full["k"],
                                   rtol=2e-5, atol=2e-5)
        # distinct layers, distinct keys: no layer was written into another
        k = np.asarray(ring["k"])[:, 0, :5]
        for a in range(cfg.n_layers):
            for b in range(a + 1, cfg.n_layers):
                assert not np.allclose(k[a], k[b])


class TestBucketedScheduler:
    @pytest.fixture(scope="class")
    def small_model(self, models):
        return models["qwen2-1.5b"]

    def _mixed_outputs(self, cls, params, cfg, prompts, **cfg_kw):
        eng = cls(params, cfg, EngineConfig(**cfg_kw))
        for i, (p, mnt) in enumerate(prompts):
            eng.submit(p, SamplingParams(max_new_tokens=mnt), uid=i)
        done = eng.run()
        return eng, {r.uid: tuple(r.output) for r in done}

    def test_bit_identity_and_compile_bound(self, small_model):
        """Bucketed admits must reproduce the serial path token for token at
        temperature 0, while its prefill compile cache stays within the
        O(log prefill_chunk) bucket bound (the serial cache grows per
        distinct length)."""
        cfg, params = small_model
        rng = np.random.default_rng(1)
        # queue (9) deeper than slots (3); lengths exercise: 1 token, short,
        # longer than prefill_chunk (8), longer than capacity (32)
        lens = (1, 3, 9, 4, 20, 2, 40, 6, 12)
        prompts = [(rng.integers(1, 500, size=n).tolist(), 5) for n in lens]
        eng_s, out_s = self._mixed_outputs(
            SerialAdmitEngine, params, cfg, prompts,
            max_slots=3, capacity=32, prefill_chunk=8, decode_chunk=4)
        eng_b, out_b = self._mixed_outputs(
            ServingEngine, params, cfg, prompts,
            max_slots=3, capacity=32, prefill_chunk=8, decode_chunk=4)
        assert out_s == out_b
        stats = eng_b.compile_stats()
        bound = stats["prefill_bucket_bound"]
        assert bound == 4  # log2(8) + 1
        assert stats["n_prefill_compiles"] <= bound
        assert all(L & (L - 1) == 0 and L <= 8
                   for L in stats["prefill_bucket_lengths"])
        # the serial baseline's cache is per-length (here: every clipped
        # distinct length), which is exactly what bucketing bounds away
        assert eng_s.compile_stats()["n_prefill_compiles"] == len(
            {min(n, 32) for n in lens})

    def test_warmup_precompiles_everything(self, small_model):
        """After warmup() no serving workload may add a prefill or decode
        compile — the dispatch set really is closed and bounded."""
        cfg, params = small_model
        eng = ServingEngine(params, cfg, EngineConfig(
            max_slots=2, capacity=32, prefill_chunk=8, decode_chunk=4))
        eng.warmup()
        before = eng.compile_stats()
        assert before["prefill_bucket_lengths"] == [1, 2, 4, 8]
        rng = np.random.default_rng(2)
        for i, n in enumerate((1, 5, 13, 40, 7)):
            eng.submit(rng.integers(1, 500, size=n).tolist(),
                       SamplingParams(max_new_tokens=3), uid=i)
        assert len(eng.run()) == 5
        after = eng.compile_stats()
        assert after["prefill_bucket_lengths"] == before["prefill_bucket_lengths"]
        assert after["decode_chunk_lengths"] == before["decode_chunk_lengths"]

    def test_prompt_longer_than_capacity(self, small_model):
        """Prompts are clipped to the last `capacity` tokens; the clipped
        tail must drive generation identically across schedulers."""
        cfg, params = small_model
        rng = np.random.default_rng(3)
        prompt = rng.integers(1, 500, size=50).tolist()
        outs = {}
        for cls in (SerialAdmitEngine, ServingEngine):
            eng = cls(params, cfg, EngineConfig(max_slots=1, capacity=16,
                                                prefill_chunk=8))
            eng.submit(prompt, SamplingParams(max_new_tokens=4), uid=0)
            outs[cls] = eng.run()[0].output
            assert len(outs[cls]) == 4
        assert outs[SerialAdmitEngine] == outs[ServingEngine]

    def test_eos_on_prefill_sampled_token(self, small_model):
        """If the very first generated token is EOS the request finishes at
        admission and the slot is immediately reusable."""
        cfg, params = small_model
        probe = ServingEngine(params, cfg, EngineConfig(max_slots=1,
                                                        capacity=32))
        probe.submit([5, 9, 17, 2], SamplingParams(max_new_tokens=1), uid=0)
        eos = probe.run()[0].output[0]
        eng = ServingEngine(params, cfg, EngineConfig(
            max_slots=1, capacity=32, prefill_chunk=8, eos_id=eos))
        eng.submit([5, 9, 17, 2], SamplingParams(max_new_tokens=64), uid=0)
        eng.submit([1, 2, 3], SamplingParams(max_new_tokens=2), uid=1)
        done = {r.uid: r for r in eng.run()}
        assert done[0].done and done[0].output == [eos]
        assert done[1].done and len(done[1].output) == 2

    def test_max_new_tokens_1(self, small_model):
        """max_new_tokens=1 finishes at prefill: no decode dispatch needed."""
        cfg, params = small_model
        eng = ServingEngine(params, cfg, EngineConfig(max_slots=2,
                                                      capacity=32,
                                                      prefill_chunk=8))
        for i in range(3):
            eng.submit([1 + i, 2, 3], SamplingParams(max_new_tokens=1),
                       uid=i)
        done = eng.run()
        assert len(done) == 3
        assert all(len(r.output) == 1 and r.done for r in done)
        assert eng.steps == 0  # never decoded

    def test_long_prompt_interleaves_with_decode(self, small_model):
        """A long prompt admitted mid-flight must not stall a decoding slot:
        the decoder's output is unchanged and the engine interleaves decode
        chunks between the long prompt's prefill chunks."""
        cfg, params = small_model
        solo = ServingEngine(params, cfg, EngineConfig(max_slots=1,
                                                       capacity=64,
                                                       prefill_chunk=8))
        solo.submit([7, 8, 9], SamplingParams(max_new_tokens=10), uid=0)
        ref = solo.run()[0].output

        eng = ServingEngine(params, cfg, EngineConfig(
            max_slots=2, capacity=64, prefill_chunk=8, decode_chunk=4))
        eng.submit([7, 8, 9], SamplingParams(max_new_tokens=10), uid=0)
        eng.step()  # uid 0 is decoding now
        rng = np.random.default_rng(4)
        eng.submit(rng.integers(1, 500, size=40).tolist(),
                   SamplingParams(max_new_tokens=3), uid=1)
        decode_steps_before = eng.steps
        done = {r.uid: r for r in eng.run()}
        assert done[0].output == ref  # decoder unaffected by the long admit
        assert len(done[1].output) == 3
        # decode advanced while the 40-token prompt was still chunking
        # (40 tokens / chunk 8 = 5 prefill steps, decode ran throughout)
        assert eng.prefill_steps >= 5
        assert eng.steps > decode_steps_before

    @pytest.mark.parametrize("kv_layout", ["ring", "paged"])
    def test_reused_slot_reads_nothing_stale(self, small_model, kv_layout):
        """A slot reused after a longer request holds that request's keys
        in ring slots past the new prompt; the new request must read none
        of them: its tokens equal those of a fresh engine."""
        cfg, params = small_model
        rng = np.random.default_rng(5)
        long_p = rng.integers(1, 500, size=40).tolist()
        short_p = rng.integers(1, 500, size=5).tolist()
        kw = dict(max_slots=1, capacity=64, prefill_chunk=8, decode_chunk=4,
                  kv_layout=kv_layout, page_size=16)

        def serve(prompts):
            eng = ServingEngine(params, cfg, EngineConfig(**kw))
            hs = [eng.submit(p, SamplingParams(max_new_tokens=n))
                  for p, n in prompts]
            eng.run()
            return [h.output for h in hs]

        reused = serve([(long_p, 20), (short_p, 12)])[1]
        assert reused == serve([(short_p, 12)])[0]
        assert len(reused) == 12

    def test_empty_prompt_rejected(self, small_model):
        cfg, params = small_model
        eng = ServingEngine(params, cfg, EngineConfig(max_slots=1,
                                                      capacity=16))
        with pytest.raises(ValueError):
            eng.submit([], SamplingParams(max_new_tokens=2), uid=0)
