"""Flash chunk-prefill attention: backend parity, exact masks, O(L·tile).

Three layers of guarantee, matching the package contract
(``repro.kernels.chunk_attention``):

  * **parity** — Pallas (interpret mode) and the streaming tile-loop
    fallback match the materialized oracle within float tolerance across
    GQA ratios, sliding-window + ring-wrap, length-0 padded rows, and the
    L = 1 decode case (floats may reorder; a tolerance gate is the honest
    comparison for online vs one-shot softmax);
  * **exact masks** — the *visible set* every backend realizes is probed
    key-by-key and must equal a first-principles brute force bit for bit,
    including the write-then-attend decode equivalence (the slot a token's
    own write evicts is invisible);
  * **footprint** — the streaming path never materializes the
    (L, cap + L) score block: asserted structurally on the jaxpr, not just
    benched, plus the analytic ``tracked_block_bytes`` accounting.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.chunk_attention.ops import (_select_tile, chunk_attention,
                                               lane_tile,
                                               tracked_block_bytes)
from repro.kernels.chunk_attention.ref import (chunk_attention_ref,
                                               chunk_mask, history_mask,
                                               reach_of, to_storage)

# the op reads a stacked ring at a layer index: cases put their ring at
# LAYER of a two-layer stack whose other layer holds other values at the
# same positions, so a read of the wrong layer changes the output
LAYER = 1


def stack(k, ks, v, vs, pos):
    """One layer's logical ring leaves (rows = batch rows or physical
    pages) → two-layer stacked storage with this ring at ``LAYER``."""
    def two(a, other):
        return None if a is None else jnp.stack([other, a])
    return to_storage(two(k, -k), two(ks, None if ks is None else 2 * ks),
                      two(v, -v), two(vs, None if vs is None else 2 * vs),
                      two(pos, pos))


def stored(args):
    """A ``make_case`` input with its ring in the op's stacked storage."""
    q, kn, vn, kc, ks, vc, vs, pb, positions, lengths = args
    return (q, kn, vn, *stack(kc, ks, vc, vs, pb), positions, lengths)


def make_case(rng, b, L, kv, g, hd, cap, *, int8=True, wrap=False,
              lengths=None):
    """A random op input with a coherent ring: the last min(pos0, cap)
    positions before the chunk start are resident (wrap=True starts past
    cap so the ring has wrapped at least once)."""
    q = jnp.asarray(rng.standard_normal((b, L, kv, g, hd)), jnp.float32)
    kn = jnp.asarray(rng.standard_normal((b, L, kv, hd)), jnp.float32)
    vn = jnp.asarray(rng.standard_normal((b, L, kv, hd)), jnp.float32)
    if int8:
        kc = jnp.asarray(rng.integers(-127, 128, (b, cap, kv, hd)), jnp.int8)
        vc = jnp.asarray(rng.integers(-127, 128, (b, cap, kv, hd)), jnp.int8)
        ks = jnp.asarray(rng.uniform(0.005, 0.02, (b, cap, kv)), jnp.float32)
        vs = jnp.asarray(rng.uniform(0.005, 0.02, (b, cap, kv)), jnp.float32)
    else:
        kc = jnp.asarray(rng.standard_normal((b, cap, kv, hd)), jnp.float32)
        vc = jnp.asarray(rng.standard_normal((b, cap, kv, hd)), jnp.float32)
        ks = vs = None
    pb = np.full((b, cap), -1, np.int64)
    pos0 = np.zeros((b,), np.int64)
    for r in range(b):
        pos0[r] = (cap + rng.integers(1, cap) if wrap
                   else rng.integers(0, cap))
        for p in range(max(0, pos0[r] - cap), pos0[r]):
            pb[r, p % cap] = p
    positions = pos0[:, None] + np.arange(L)[None, :]
    if lengths is None:
        lengths = rng.integers(0, L + 1, (b,))
    return (q, kn, vn, kc, ks, vc, vs, jnp.asarray(pb, jnp.int32),
            jnp.asarray(positions, jnp.int32),
            jnp.asarray(lengths, jnp.int32))


CASES = [
    # (b, L, kv, g, hd, cap, window, int8, wrap)   — GQA ratios, windows,
    pytest.param(2, 8, 2, 2, 16, 32, None, True, False, id="gqa2x2-full"),
    pytest.param(2, 8, 1, 4, 16, 32, None, True, True, id="gqa1x4-wrap"),
    pytest.param(2, 8, 4, 1, 16, 32, 8, True, True, id="mha-window-wrap"),
    pytest.param(2, 6, 1, 3, 8, 24, 5, True, True, id="window5-wrap"),
    pytest.param(3, 1, 2, 2, 8, 16, None, True, True, id="decode-L1"),
    pytest.param(3, 1, 2, 2, 8, 16, 8, True, True, id="decode-L1-window"),
    pytest.param(2, 4, 2, 2, 8, 16, None, False, False, id="float-cache"),
]


class TestBackendParity:
    @pytest.mark.parametrize("backend", ["stream", "pallas"])
    @pytest.mark.parametrize("b,L,kv,g,hd,cap,window,int8,wrap", CASES)
    def test_matches_materialized_oracle(self, backend, b, L, kv, g, hd,
                                         cap, window, int8, wrap):
        """Online-softmax backends vs the materialized reference: the
        tolerance gate covers softmax reordering only — valid rows must
        agree to float-roundoff, not merely 'roughly'."""
        rng = np.random.default_rng(hash((b, L, kv, cap, int8)) % 2**31)
        args = make_case(rng, b, L, kv, g, hd, cap, int8=int8, wrap=wrap)
        ref = np.asarray(chunk_attention_ref(*args, window=window))
        got = np.asarray(chunk_attention(*stored(args), layer=LAYER,
                                         window=window, backend=backend,
                                         tile=8))
        lengths = np.asarray(args[-1])
        for r in range(b):
            if lengths[r] or int(jnp.sum(args[7][r] >= 0)):  # anything visible
                np.testing.assert_allclose(
                    got[r, :max(lengths[r], 1)], ref[r, :max(lengths[r], 1)],
                    rtol=2e-5, atol=2e-5, err_msg=f"row {r}")

    def test_zero_length_rows_are_finite(self):
        """length-0 rows (free/decoding slots riding through a prefill
        dispatch) must come out finite on every backend — garbage is fine,
        NaN would poison the residual stream."""
        rng = np.random.default_rng(0)
        args = make_case(rng, 2, 4, 2, 2, 8, 16,
                         lengths=np.zeros((2,), np.int64))
        # empty ring too: nothing visible at all
        args = args[:7] + (jnp.full_like(args[7], -1),) + args[8:]
        for backend in ("stream", "pallas", "materialized"):
            out = np.asarray(chunk_attention(*stored(args), layer=LAYER,
                                             backend=backend, tile=4))
            assert np.isfinite(out).all(), backend


def _visible_sets(op_out, n_keys):
    """Recover per-(row, query) visible key sets from probe outputs:
    ``op_out[s]`` is the op result with v == 1 at key s and 0 elsewhere,
    so key s is visible to (r, l) iff the output is positive."""
    b, L = op_out.shape[1], op_out.shape[2]
    vis = np.zeros((b, L, n_keys), bool)
    for s in range(n_keys):
        vis[:, :, s] = op_out[s, :, :, 0, 0, 0] > 1e-9
    return vis


class TestExactMasks:
    """The visible set is the exact part of the contract: probe it key by
    key (constant scores → uniform weights → a key's indicator value
    survives iff it is visible) and compare bit-for-bit."""

    @pytest.mark.parametrize("window", [None, 5, 8])
    @pytest.mark.parametrize("wrap", [False, True])
    def test_backends_realize_identical_visible_sets(self, window, wrap):
        b, L, kv, g, hd, cap = 2, 5, 1, 1, 4, 12
        rng = np.random.default_rng(7)
        base = make_case(rng, b, L, kv, g, hd, cap, int8=False, wrap=wrap)
        (q, kn, vn, kc, _, vc, _, pb, positions, lengths) = base
        zeros = jnp.zeros_like
        outs = {}
        for backend in ("materialized", "stream", "pallas"):
            probes = []
            for s in range(cap + L):
                v_ring = np.zeros((b, cap, kv, hd), np.float32)
                v_new = np.zeros((b, L, kv, hd), np.float32)
                if s < cap:
                    v_ring[:, s] = 1.0
                else:
                    v_new[:, s - cap] = 1.0
                probes.append(np.asarray(chunk_attention(*stored((
                    zeros(q), zeros(kn), jnp.asarray(v_new), zeros(kc), None,
                    jnp.asarray(v_ring), None, pb, positions, lengths)),
                    layer=LAYER, window=window, backend=backend, tile=4)))
            outs[backend] = _visible_sets(np.stack(probes), cap + L)

        # first-principles brute force of the contract rule
        reach = reach_of(cap, window)
        pbn, pos, lens = map(np.asarray, (pb, positions, lengths))
        expect = np.zeros((b, L, cap + L), bool)
        for r in range(b):
            for l in range(L):
                for s in range(cap):
                    d = pos[r, l] - pbn[r, s]
                    expect[r, l, s] = pbn[r, s] >= 0 and 0 <= d < reach
                for j in range(L):
                    d = pos[r, l] - pos[r, j]
                    expect[r, l, cap + j] = j < lens[r] and 0 <= d < reach
        # the op's own mask helpers must agree with the brute force too
        np.testing.assert_array_equal(
            np.asarray(history_mask(pb, positions, reach)), expect[:, :, :cap])
        np.testing.assert_array_equal(
            np.asarray(chunk_mask(positions, lengths, reach)),
            expect[:, :, cap:])
        for backend, vis in outs.items():
            # compare only queries that see anything (all-masked rows are
            # defined-garbage: uniform for materialized, zero for online)
            any_vis = expect.any(-1)
            np.testing.assert_array_equal(vis[any_vis], expect[any_vis],
                                          err_msg=backend)

    def test_L1_reproduces_write_then_attend_decode(self):
        """The L = 1 masks equal the pre-PR-5 decode semantics (write the
        token into the ring, then attend the post-write ring): the entry at
        distance exactly cap — the one the write evicts — is invisible,
        everything else the old mask admitted is visible."""
        cap, window = 8, None
        for pos0 in (3, 8, 19):  # pre-wrap, boundary, wrapped
            pb = np.full((1, cap), -1, np.int64)
            for p in range(max(0, pos0 - cap), pos0):
                pb[0, p % cap] = p
            positions = np.asarray([[pos0]])
            reach = reach_of(cap, window)
            vis_new = np.asarray(history_mask(
                jnp.asarray(pb, jnp.int32), jnp.asarray(positions, jnp.int32),
                reach))[0, 0]
            # old semantics: write pos0 into slot pos0 % cap, then mask
            # (pc >= 0) & (pc <= pos) & (pos - pc < cap + 1)
            pb_post = pb.copy()
            pb_post[0, pos0 % cap] = pos0
            vis_old = ((pb_post[0] >= 0) & (pb_post[0] <= pos0)
                       & (pos0 - pb_post[0] < cap + 1))
            # post-write slot pos0%cap holds the token itself == the op's
            # in-chunk self key; ring visibility must match elsewhere
            self_slot = pos0 % cap
            np.testing.assert_array_equal(
                np.delete(vis_new, self_slot), np.delete(vis_old, self_slot),
                err_msg=f"pos0={pos0}")
            assert not vis_new[self_slot]  # evicted entry masked pre-write
            assert vis_old[self_slot]      # ...because old path read the
            # freshly written token there; the op reads it as the self key
            chunk_vis = np.asarray(chunk_mask(
                jnp.asarray(positions, jnp.int32),
                jnp.asarray([1], jnp.int32), reach))[0, 0, 0]
            assert chunk_vis


def _collect_eqns(jaxpr, out):
    for eqn in jaxpr.eqns:
        out.append(eqn)
        for v in eqn.params.values():
            _collect_sub(v, out)


def _collect_sub(v, out):
    if hasattr(v, "eqns"):
        _collect_eqns(v, out)
    elif hasattr(v, "jaxpr"):
        _collect_eqns(v.jaxpr, out)
    elif isinstance(v, (list, tuple)):
        for x in v:
            _collect_sub(x, out)


def _eqn_shapes(fn, *args, **kw):
    eqns = []
    _collect_eqns(jax.make_jaxpr(lambda *a: fn(*a, **kw))(*args).jaxpr, eqns)
    shapes = []
    for eqn in eqns:
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            if aval is not None and getattr(aval, "shape", None) is not None:
                shapes.append((tuple(aval.shape),
                               np.dtype(aval.dtype).itemsize
                               * int(np.prod(aval.shape)) if aval.shape
                               else 0))
    return shapes


class TestStreamingFootprint:
    B, L, KV, G, HD, CAP = 2, 8, 2, 4, 16, 256

    def _args(self, cap):
        rng = np.random.default_rng(1)
        return stored(make_case(rng, self.B, self.L, self.KV, self.G,
                                self.HD, cap))

    def test_no_full_score_block_in_jaxpr(self):
        """Structural, not benched: the streaming jaxpr contains no
        intermediate with the (…, L, cap + L) score-block shape (the
        materialized jaxpr does), and its largest intermediate is strictly
        smaller."""
        cap, L = self.CAP, self.L
        args = self._args(cap)
        tile = 16
        full_block = {s for s, _ in _eqn_shapes(
            chunk_attention, *args, backend="materialized")
            if s[-1:] == (cap + L,)}
        assert full_block, "materialized path must build the full block"
        stream_shapes = _eqn_shapes(chunk_attention, *args,
                                    backend="stream", tile=tile)
        assert not any(s[-1:] == (cap + L,) or s[-1:] == (cap,)
                       for s, _ in stream_shapes
                       if len(s) >= 4), \
            "streaming path materialized a full-width score block"
        max_stream = max(nb for _, nb in stream_shapes)
        max_mat = max(nb for _, nb in _eqn_shapes(
            chunk_attention, *args, backend="materialized"))
        assert max_stream < max_mat

    def test_tracked_bytes_are_O_L_tile(self):
        """The analytic accounting the benchmark reports: streaming bytes
        stop growing with capacity once the tile saturates; materialized
        bytes grow linearly with capacity."""
        b, kv, g, L = self.B, self.KV, self.G, self.L
        stream = [tracked_block_bytes(b, kv, g, L, cap, backend="stream")
                  for cap in (1024, 2048, 4096)]
        mat = [tracked_block_bytes(b, kv, g, L, cap, backend="materialized")
               for cap in (1024, 2048, 4096)]
        assert stream[0] == stream[1] == stream[2]  # O(L·tile), cap-free
        assert mat[1] > 2 * mat[0] * 0.9 and mat[2] > 2 * mat[1] * 0.9
        tile = _select_tile(4096, L)
        assert stream[2] == 4 * b * kv * g * L * tile
        assert stream[2] * 4 <= mat[2]  # the structural win at 4k context

    def test_decode_uses_single_tile(self):
        """L = 1 must not pay loop machinery: tile selection hands decode
        the whole ring as one tile (the decode fast path)."""
        assert _select_tile(4096, 1) == 4096
        assert _select_tile(256, 64) < 256

    @pytest.mark.parametrize("slots", [16, 96, 2048, 4096, 4224])
    @pytest.mark.parametrize("L", [1, 16, 64, 128, 256, 1024])
    def test_lane_tile_is_legal(self, slots, L):
        """The compiled kernel's tile divides the slots and is a multiple of
        128 lanes or all of them, never below the budget tile."""
        t0 = _select_tile(slots, L)
        t = lane_tile(slots, t0)
        assert slots % t == 0 and (t % 128 == 0 or t == slots)
        assert t >= t0
        if t0 % 128 == 0:
            assert t == t0


class TestModelLevelBackends:
    """The rewired model paths agree across backends (tolerance-gated) and
    the engine threads EngineConfig.attn_backend through."""

    def test_prefill_chunk_backend_equivalence(self):
        from repro import configs
        from repro.models import init_decode_state, init_params, prefill_chunk

        base = configs.get_smoke_config("qwen2-1.5b").scaled(
            kv_cache_dtype="int8")
        params = init_params(base, jax.random.PRNGKey(0))
        rng = np.random.default_rng(3)
        toks = jnp.asarray(rng.integers(1, 500, (2, 8)), jnp.int32)
        lens = jnp.asarray([8, 5], jnp.int32)
        outs = {}
        for backend in ("stream", "materialized"):
            cfg = base.scaled(attn_backend=backend)
            st = init_decode_state(cfg, 2, 16)
            lg, st = prefill_chunk(params, cfg, st, {"tokens": toks}, lens)
            outs[backend] = (np.asarray(lg, np.float32), st)
        np.testing.assert_allclose(outs["stream"][0], outs["materialized"][0],
                                   rtol=2e-4, atol=2e-4)
        # ring bookkeeping (positions written/dropped) is backend-exact;
        # k/v payloads beyond layer 0 inherit the activations' float drift
        sa, sb = outs["stream"][1], outs["materialized"][1]
        for key in ("pos",):
            assert jnp.array_equal(sa[key], sb[key])
        assert jnp.array_equal(sa["blocks"]["b0"]["pos"],
                               sb["blocks"]["b0"]["pos"])
        for leaf_a, leaf_b in zip(jax.tree.leaves(sa), jax.tree.leaves(sb)):
            np.testing.assert_allclose(
                np.asarray(leaf_a, np.float32), np.asarray(leaf_b, np.float32),
                rtol=2e-3, atol=1.01)  # int8 leaves may flip one step

    def test_engine_threads_attn_backend(self):
        from repro import configs
        from repro.models import init_params
        from repro.serving import EngineConfig, SamplingParams, ServingEngine

        cfg = configs.get_smoke_config("qwen2-1.5b")
        params = init_params(cfg, jax.random.PRNGKey(0))
        eng = ServingEngine(params, cfg, EngineConfig(
            max_slots=1, capacity=16, attn_backend="materialized"))
        assert eng.cfg.attn_backend == "materialized"
        h = eng.submit([5, 9, 17], SamplingParams(max_new_tokens=2))
        assert len(h.result().tokens) == 2

    def test_memory_stats_accounting(self):
        from repro import configs
        from repro.core.ptqtp import PTQTPConfig
        from repro.core.quantize_model import quantize_tree
        from repro.models import init_params
        from repro.serving import EngineConfig, ServingEngine

        cfg = configs.get_smoke_config("qwen2-1.5b")
        params = init_params(cfg, jax.random.PRNGKey(0))
        qp, _ = quantize_tree(params, PTQTPConfig(group_size=32, t_max=2))
        eng = ServingEngine(qp, cfg, EngineConfig(max_slots=2, capacity=32,
                                                  preunpack_decode=True))
        mem = eng.memory_stats()
        assert mem["preunpack_decode"]
        # unpacked planes are int8 trits: exactly 4x the 2-bit packed bytes
        assert mem["resident_plane_bytes"] == 4 * mem["packed_plane_bytes"]
        assert mem["preunpack_ratio"] == pytest.approx(4.0)
        assert mem["resident_total_bytes"] >= (mem["resident_plane_bytes"]
                                               + mem["decode_state_bytes"])
        off = ServingEngine(qp, cfg, EngineConfig(max_slots=2, capacity=32,
                                                  preunpack_decode=False))
        assert off.memory_stats()["preunpack_ratio"] == pytest.approx(1.0)
