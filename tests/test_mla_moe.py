"""Latent attention (MLA), YaRN rope, the grouped expert kernel and no-drop
MoE serving routing (DeepSeek-V2), at test size on the CPU.

* MLA: chunked prefill then decode through the latent ring, absorbed and
  read by ``latent_attention``, equals the un-absorbed attention computed
  here from the layer's own weights, ring wrap included; the model's
  engine path equals its training forward.
* YaRN: the inverse-frequency ramp bounds and the softmax scale against
  the published formulas, written out here.
* ``expert_matmul`` (Pallas, interpret mode, and XLA) against the
  per-expert ternary oracle, with empty experts, one expert taking every
  row, and a row count that is not a multiple of the tile, each read in
  place at every layer of a three-layer stack.
* ``moe_serve``: a token's output is bit-equal whatever shares its batch
  (padding rows included), the top-k gates are not renormalized, and
  every valid token's k assignments are computed.
* The engine: its MoE counters and dispatch-span args count the routed
  rows, its memory accounting counts the latent ring, and the paged
  layout refuses a latent cache.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.packing import pack_trits
from repro.core.quantize_model import quantize_tree
from repro.kernels.chunk_attention.latent import latent_attention
from repro.kernels.expert_matmul import TILE_ROWS, expert_matmul
from repro.kernels.ternary_matmul.ref import ternary_matmul_packed_ref
from repro.models import (decode_step, forward, init_decode_state,
                          init_params, prefill_chunk)
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models.common import (YarnRope, rope_freqs, rope_scale,
                                 yarn_correction_range)
from repro.serving import EngineConfig, SamplingParams, ServingEngine
from repro.serving.observability import Observability

ARCH = "deepseek-v2-lite"


def _cfg():
    return configs.get_smoke_config(ARCH)


def _params(seed=1):
    return init_params(_cfg(), jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# MLA
# ---------------------------------------------------------------------------

def _unabsorbed(p, cfg, x, positions, reach):
    """Un-absorbed MLA of x (B, S, D) at absolute positions (S,): every
    query sees the keys 0 <= p - s < reach, keys and values from the
    latent through W_kv_b per head."""
    b, s, _ = x.shape
    h, r, nope = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    q_nope, q_pe, lat = mla_mod._project(p, cfg, x, positions)
    kv = (lat[..., :r] @ p["wkv_b"]["kernel"]).reshape(b, s, h, -1)
    q = jnp.concatenate([q_nope, q_pe], -1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        lat[..., None, r:], q_pe.shape)], -1)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) * mla_mod.softmax_scale(cfg)
    d = positions[:, None] - positions[None, :]
    sc = jnp.where(((d >= 0) & (d < reach))[None, None], sc, -1e30)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), kv[..., nope:])
    return o.reshape(b, s, -1) @ p["wo"]["kernel"]


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("cap", [32, 8])        # 8 < 14 tokens: ring wraps
def test_mla_chunks_then_decode_equal_unabsorbed(backend, cap):
    cfg = _cfg().scaled(attn_backend=backend)
    p = _params()["prefix"]["p0"]["attn"]
    rng = np.random.default_rng(0)
    b, s = 2, 14
    x = jnp.asarray(rng.standard_normal((b, s, cfg.d_model)), jnp.float32)
    pos = jnp.arange(s)
    want = _unabsorbed(p, cfg, x, pos, cap)
    cache = mla_mod.latent_cache_init(cfg, b, cap, jnp.float32)
    got = []
    for lo, hi in ((0, 5), (5, 8), (8, 11)):        # prefill chunks
        n = hi - lo
        y, cache = mla_mod.mla_chunk(
            p, cfg, cache, x[:, lo:hi],
            jnp.broadcast_to(pos[lo:hi], (b, n)), jnp.full((b,), n))
        got.append(y)
    for t in range(11, s):                           # decode
        y, cache = mla_mod.mla_decode(p, cfg, cache, x[:, t],
                                      jnp.full((b,), t))
        got.append(y[:, None])
    np.testing.assert_allclose(np.asarray(jnp.concatenate(got, 1)),
                               np.asarray(want), rtol=2e-4, atol=2e-5)


def test_mla_inactive_row_leaves_its_ring():
    cfg = _cfg()
    p = _params()["prefix"]["p0"]["attn"]
    cache = mla_mod.latent_cache_init(cfg, 2, 16, jnp.float32)
    x = jnp.ones((2, cfg.d_model), jnp.float32)
    _, new = mla_mod.mla_decode(p, cfg, cache, x, jnp.asarray([3, 4]),
                                active=jnp.asarray([True, False]))
    assert int(new["pos"][0, 3]) == 3 and int(new["pos"][1].max()) == -1
    assert not bool(jnp.any(new["latent"][1]))
    assert cache["latent"].shape[-1] % 128 == 0      # lane-aligned storage


def test_engine_path_equals_forward_logits():
    """prefill_chunk in pieces then decode_step, logits at every position,
    against the training forward (un-absorbed, capacity routing in exact
    mode), and the MoE counts of each call."""
    cfg, params = _cfg(), _params(3)
    rng = np.random.default_rng(3)
    b, s = 2, 12
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)
    full = np.asarray(forward(params, cfg, {"tokens": toks}))
    st = init_decode_state(cfg, b, 32)
    moe_layers = cfg.n_layers - len(cfg.prefix_pattern)
    k = cfg.moe.top_k
    lens = jnp.asarray([5, 3], jnp.int32)             # ragged first chunk
    lg, st = prefill_chunk(params, cfg, st, {"tokens": toks[:, :5]}, lens)
    assert st.pop("moe_stats").tolist()[0] == k * moe_layers * 8
    np.testing.assert_allclose(np.asarray(lg[0]), full[0, 4], atol=2e-4)
    np.testing.assert_allclose(np.asarray(lg[1]), full[1, 2], atol=2e-4)
    lg, st = prefill_chunk(params, cfg, st, {"tokens": jnp.stack(
        [toks[0, 5:8], toks[1, 3:6]])}, jnp.asarray([3, 3], jnp.int32))
    st.pop("moe_stats")
    np.testing.assert_allclose(np.asarray(lg[0]), full[0, 7], atol=2e-4)
    np.testing.assert_allclose(np.asarray(lg[1]), full[1, 5], atol=2e-4)
    lg, st = decode_step(params, cfg, st, jnp.asarray([toks[0, 8],
                                                       toks[1, 6]]),
                         active=jnp.asarray([True, False]))
    assert st.pop("moe_stats").tolist()[0] == k * moe_layers * 1
    np.testing.assert_allclose(np.asarray(lg[0]), full[0, 8], atol=2e-4)


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------

def test_yarn_ramp_and_softmax_scale_follow_the_published_formulas():
    cfg = configs.get_config(ARCH)
    y = cfg.rope_yarn
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    # correction dim of r rotations: d · ln(L / (2π r)) / (2 ln θ)
    corr = lambda r: dim * math.log(4096 / (2 * math.pi * r)) / (
        2 * math.log(base))
    assert (math.floor(corr(32)), math.ceil(corr(1))) == (10, 23)
    assert yarn_correction_range(y, dim, base) == (10, 23)
    f = np.asarray(rope_freqs(dim, base, y), np.float64)
    orig = base ** -(np.arange(0, dim, 2) / dim)
    np.testing.assert_allclose(f[:11], orig[:11], rtol=1e-6)   # ramp 0
    np.testing.assert_allclose(f[23:], orig[23:] / 40, rtol=1e-6)
    mid = 16
    w = (mid - 10) / 13
    np.testing.assert_allclose(f[mid], orig[mid] * (1 - w) + orig[mid] / 40
                               * w, rtol=1e-6)
    scale = 192 ** -0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2
    assert mla_mod.softmax_scale(cfg) == pytest.approx(scale, rel=1e-12)
    assert scale == pytest.approx(0.11472, abs=1e-5)
    # equal mscales: cos and sin are not rescaled
    assert rope_scale(y) == 1.0
    assert rope_scale(YarnRope(40.0, 4096, mscale=1.0)) == pytest.approx(
        0.1 * math.log(40) + 1)


# ---------------------------------------------------------------------------
# the grouped expert kernel
# ---------------------------------------------------------------------------

def _stack(rng, e, n, d, g=128):
    t = rng.integers(-1, 2, (2, e, n, d)).astype(np.int8)
    pk = [jnp.stack([pack_trits(jnp.asarray(m)) for m in t[i]])
          for i in range(2)]
    alpha = jnp.asarray(rng.uniform(0.01, 0.05, (e, n, d // g, 2)),
                        jnp.float32)
    return pk[0], pk[1], alpha


def _tiles(row_experts, e):
    """Rows sorted by expert into tiles: (x row of each assignment, tile →
    expert map, live tiles, buffer rows)."""
    counts = np.bincount(row_experts, minlength=e)
    tiles = -(-counts // TILE_ROWS)
    first = (np.cumsum(tiles) - tiles) * TILE_ROWS
    order = np.argsort(row_experts, kind="stable")
    slot = np.empty_like(order)
    seen = np.zeros(e, int)
    for i in order:
        ex = row_experts[i]
        slot[i] = first[ex] + seen[ex]
        seen[ex] += 1
    live = int(tiles.sum())
    te = np.repeat(np.arange(e), tiles)
    n_tiles = live + 2                                # two dead tiles
    te = np.concatenate([te, np.full(n_tiles - live, te[-1])])
    return slot, te.astype(np.int32), live, n_tiles * TILE_ROWS


@pytest.mark.parametrize("backend", ["pallas", "xla"])
@pytest.mark.parametrize("case", ["spread", "one_expert", "ragged"])
@pytest.mark.parametrize("n,d", [(256, 384), (384, 256)])
@pytest.mark.parametrize("layer", [0, 1, 2])
def test_expert_matmul_matches_per_expert_oracle(backend, case, n, d, layer):
    """A stack of three layers' experts, read in place at ``layer``: every
    row equals the oracle of that layer's own planes."""
    rng = np.random.default_rng(7)
    e = 4
    rows = {"spread": np.array([0, 3, 3, 0, 3] * 7),   # experts 1, 2 empty
            "one_expert": np.full(40, 2),
            "ragged": rng.integers(0, e, 37)}[case]
    t1, t2, alpha = (jnp.stack(a) for a in zip(
        *(_stack(rng, e, n, d) for _ in range(3))))
    x_rows = rng.standard_normal((len(rows), d)).astype(np.float32)
    slot, te, live, r = _tiles(rows, e)
    x = np.zeros((r, d), np.float32)
    x[slot] = x_rows
    y = np.asarray(expert_matmul(jnp.asarray(x), t1, t2, alpha,
                                 jnp.asarray(te), jnp.asarray([live]),
                                 jnp.asarray(layer, jnp.int32),
                                 group_size=128, backend=backend))
    for ex in range(e):
        mine = rows == ex
        if not mine.any():
            continue
        want = ternary_matmul_packed_ref(
            jnp.asarray(x_rows[mine]), t1[layer, ex], t2[layer, ex],
            alpha[layer, ex], 128)
        np.testing.assert_allclose(y[slot[mine]], np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# no-drop serving routing
# ---------------------------------------------------------------------------

def _moe_params(quantized):
    p = _params(5)["blocks"]["b0"]["moe"]
    p = jax.tree.map(lambda a: a[0], p)               # one layer
    if quantized:
        p, _ = quantize_tree(p)
    return p


@pytest.mark.parametrize("routed", [0, 1, 7, 40])
def test_expert_tiles_name_real_experts_only(routed):
    """Every tile names a real expert, also where nothing is routed (the
    kernel reads the named expert's planes for every tile, dead ones too,
    and a TPU does not bounds-check a block index); live tiles hold only
    their expert's rows."""
    e, tm = 8, TILE_ROWS
    rng = np.random.default_rng(routed)
    flat = np.full(48, e)
    flat[:routed] = rng.integers(0, e, routed)
    row, te, live, counts = moe_mod.expert_tiles(jnp.asarray(flat), e, tm)
    row, te, live = np.asarray(row), np.asarray(te), int(live[0])
    assert te.min() >= 0 and te.max() < e
    assert live == sum(-(-c // tm) for c in np.bincount(flat[flat < e],
                                                        minlength=e))
    assert np.all(row[routed:] == -1)
    assert len(set(row[:routed])) == routed
    for i in range(routed):
        assert te[row[i] // tm] == flat[i] and row[i] // tm < live


@pytest.mark.parametrize("quantized", [False, True])
def test_moe_output_depends_only_on_its_token(quantized):
    cfg = _cfg()
    p = _moe_params(quantized)
    rng = np.random.default_rng(11)
    t, d = 24, cfg.d_model
    mine = rng.standard_normal((d,)).astype(np.float32)
    crowd = rng.standard_normal((t, d)).astype(np.float32)
    crowd[9] = mine
    alone = np.zeros((t, d), np.float32)
    alone[9] = mine
    valid_alone = np.zeros(t, bool)
    valid_alone[9] = True
    y_crowd, st_crowd = moe_mod.moe_serve(p, jnp.asarray(crowd), cfg.moe)
    y_alone, st_alone = moe_mod.moe_serve(p, jnp.asarray(alone), cfg.moe,
                                          valid=jnp.asarray(valid_alone))
    assert np.array_equal(np.asarray(y_crowd[9]), np.asarray(y_alone[9]))
    k = cfg.moe.top_k
    assert st_crowd.tolist()[0] == k * t and st_alone.tolist()[0] == k
    assert st_alone.tolist()[1] == k * TILE_ROWS      # one tile per expert


def test_moe_gates_are_not_renormalized():
    cfg = _cfg()
    p = _moe_params(False)
    rng = np.random.default_rng(12)
    x = jnp.asarray(rng.standard_normal((6, cfg.d_model)), jnp.float32)
    y, _ = moe_mod.moe_serve(p, x, cfg.moe)
    probs = jax.nn.softmax(x @ p["router"]["kernel"], -1)
    g, e = jax.lax.top_k(probs, cfg.moe.top_k)
    assert float(jnp.max(jnp.sum(g, -1))) < 0.99     # the test can tell
    ex = p["experts"]
    sh = p["shared"]
    want = (jax.nn.silu(x @ sh["wg"]["kernel"]) * (x @ sh["wi"]["kernel"])
            @ sh["wo"]["kernel"])
    for j in range(cfg.moe.top_k):
        w = lambda m: ex[m]["kernel"][e[:, j]]
        h = jax.nn.silu(jnp.einsum("td,tdf->tf", x, w("wg"))) * jnp.einsum(
            "td,tdf->tf", x, w("wi"))
        want = want + g[:, j:j + 1] * jnp.einsum("tf,tfd->td", h, w("wo"))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-4,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

def _engine(params, **kw):
    cfg = _cfg()
    ecfg = EngineConfig(max_slots=3, capacity=64, prefill_chunk=4,
                        decode_chunk=4, **kw)
    return ServingEngine(params, cfg, ecfg,
                         observability=Observability(trace=True))


def test_engine_counts_every_routed_row_and_the_latent_ring():
    cfg = _cfg()
    params, _ = quantize_tree(_params(2))
    eng = _engine(params)
    rng = np.random.default_rng(2)
    for n, new in ((9, 6), (3, 9), (5, 4)):
        eng.submit(rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                   SamplingParams(max_new_tokens=new, temperature=0.0))
    eng.run()
    k, layers = cfg.moe.top_k, cfg.n_layers - len(cfg.prefix_pattern)
    spans = [e for e in eng.obs.trace.events()
             if e.name in ("prefill_dispatch", "decode_dispatch")]
    decode_rows = sum(e.args["n_steps"] * e.args["rows"] for e in spans
                      if e.name == "decode_dispatch")
    assert eng.moe_routed_rows == k * layers * (eng.prefill_tokens
                                                + decode_rows)
    assert sum(e.args["moe_routed_rows"] for e in spans) \
        == eng.moe_routed_rows
    assert eng.moe_tile_rows % TILE_ROWS == 0
    assert eng.moe_routed_rows <= eng.moe_tile_rows
    reg = eng.obs.registry
    assert reg.value("serving_moe_routed_rows_total") == eng.moe_routed_rows
    assert reg.value("serving_moe_tile_rows_total") == eng.moe_tile_rows
    ring = sum(int(eng.state[part][name][leaf].nbytes)
               for part, name in (("prefix", "p0"), ("blocks", "b0"))
               for leaf in ("latent", "pos"))
    assert eng.memory_stats()["kv_pool_bytes"] == ring


def test_paged_layout_refuses_a_latent_cache():
    with pytest.raises(ValueError, match="latent"):
        _engine(_params(), kv_layout="paged", page_size=16)


@pytest.mark.parametrize("wrap", [False, True])
def test_latent_attention_kernel_matches_materialized(wrap):
    rng = np.random.default_rng(4)
    nl, b, cap, h, L, c, v = 2, 3, 256, 4, 8, 256, 128
    cache = jnp.asarray(rng.standard_normal((nl, b, cap, c)), jnp.float32)
    pos0 = np.array([0, 100, 300 if wrap else 200])
    pb = np.full((nl, b, cap), -1, np.int32)
    for r, p0 in enumerate(pos0):
        for p in range(max(0, p0 - cap), p0):
            pb[:, r, p % cap] = p
    positions = jnp.asarray(pos0[:, None] + np.arange(L)[None], jnp.int32)
    lengths = jnp.asarray([8, 5, 8], jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, L, h, c)), jnp.float32)
    kn = jnp.asarray(rng.standard_normal((b, L, c)), jnp.float32)
    for layer in range(nl):
        got, want = (latent_attention(
            q, kn, cache, jnp.asarray(pb), positions, lengths, scale=0.1,
            v_dim=v, layer=layer, backend=be, tile=128)
            for be in ("pallas", "xla"))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
