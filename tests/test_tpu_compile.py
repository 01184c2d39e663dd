"""Compile the serving path's Pallas kernels for a TPU v5e, without the chip.

The TPU's compiler is installed with JAX and compiles for a chip that is
described and not attached, so these tests catch what interpret mode
cannot — block shapes off the (8, 128) tiling, too much VMEM, a program
that does not fit HBM — at qwen2-1.5b's real widths (d_model 1536, d_ff
8960, vocab 151,936, 2 kv heads of 128, group size 128), a few seconds
per compile. Nothing runs: a pass says the chip's compiler accepts the
kernel, not that it is correct (the interpret-mode parity tests say that)
or fast.

The topology is described inside a fixture so that only the worker that
runs this file loads the TPU library; the persistent compilation cache is
off around the compiles (an entry written for a described chip cannot be
read back without one).
"""

import functools
import importlib.util
import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import ptqtp
from repro.kernels.chunk_attention.kernel import (chunk_attention_paged_pallas,
                                                  chunk_attention_pallas)
from repro.kernels.chunk_attention.ops import (_select_tile, lane_tile,
                                               paged_tile)
from repro.kernels.ternary_matmul import ops as tm_ops

G = 128
# qwen2-1.5b linear shapes (n_out, d_in): q/o, k/v, gate/up, down, lm_head;
# then two n that are not a multiple of 128 lanes: phi3-vision's lm_head
# and deepseek-moe's dense gate/up
LINEAR = [(1536, 1536), (256, 1536), (8960, 1536), (1536, 8960),
          (151936, 1536), (32064, 3072), (10944, 2048)]
SLOTS, CAP, KV, GROUP, HD = 8, 4096, 2, 6, 128
LAYERS = 2  # the ring is one stack over a scan's layers, read at an index
# decode, the EngineConfig default prefill chunk, and two longer chunks
# whose default tiles (64, 32) are off the lane rule
CHUNKS = [1, 64, 128, 256]
HBM_BYTES = 16 * 1024 ** 3  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the kernel is there
    return compiled


@pytest.mark.parametrize("n,d", LINEAR)
@pytest.mark.parametrize("m", [1, 8, 256])  # decode (block_m = m), prefill
def test_ternary_matmul_compiles(one_chip, m, n, d):
    args = [_spec(one_chip, (m, d), jnp.bfloat16),
            _spec(one_chip, (n, d // 4), jnp.uint8),
            _spec(one_chip, (n, d // 4), jnp.uint8),
            _spec(one_chip, (n, d // G, 2), jnp.float32)]
    _compile(lambda x, t1, t2, a: tm_ops._pallas(
        x, t1, t2, a, G, interpret=False), *args)


def _attn_inputs(sh, L):
    return [_spec(sh, (SLOTS, KV, GROUP, L, HD), jnp.float32),
            _spec(sh, (SLOTS, L, KV, HD), jnp.bfloat16),
            _spec(sh, (SLOTS, L, KV, HD), jnp.bfloat16)]


def _storage(sh, rows, slots, dtype):
    """Stacked ring (or pool) storage as ``models.attention`` allocates it:
    k, v (layers, rows, slots, KV·hd), positions (layers, rows, slots),
    int8 scales (layers, KV, rows, slots)."""
    kv = _spec(sh, (LAYERS, rows, slots, KV * HD), dtype)
    return ([kv, kv, _spec(sh, (LAYERS, rows, slots), jnp.int32)],
            [_spec(sh, (LAYERS, KV, rows, slots), jnp.float32)] * 2)


def _pool_pages(n_pages):
    """Physical pages of a pool: the slots' pages + the null page, rounded
    up to a multiple of 8 as ``paged_cache_init`` allocates them."""
    return -(-(SLOTS * n_pages + 1) // 8) * 8


@pytest.mark.parametrize("cache_dtype", [jnp.bfloat16, jnp.int8])
@pytest.mark.parametrize("L", CHUNKS)
def test_chunk_attention_ring_compiles(one_chip, L, cache_dtype):
    int8 = cache_dtype == jnp.int8
    tile = lane_tile(CAP, _select_tile(CAP, L))
    ring, scales = _storage(one_chip, SLOTS, CAP, cache_dtype)
    args = _attn_inputs(one_chip, L) + ring + [
        _spec(one_chip, (SLOTS, L), jnp.int32),
        _spec(one_chip, (SLOTS,), jnp.int32),
        _spec(one_chip, (), jnp.int32)]

    def fn(q, kn, vn, kc, vc, pb, pos, lens, layer, *sc):
        ks, vs = sc if int8 else (None, None)
        return chunk_attention_pallas(q, kn, vn, kc, ks, vc, vs, pb, pos,
                                      lens, layer, tile=tile, interpret=False)

    _compile(fn, *args, *(scales if int8 else []))


@pytest.mark.parametrize("cache_dtype", [jnp.bfloat16, jnp.int8])
@pytest.mark.parametrize("L,page", [(1, 16), (64, 16), (256, 16),
                                    (256, 512)])
def test_chunk_attention_paged_compiles(one_chip, L, page, cache_dtype):
    int8 = cache_dtype == jnp.int8
    n_pages = CAP // page
    tile = lane_tile(page, paged_tile(page, L))
    pools, scales = _storage(one_chip, _pool_pages(n_pages), page,
                             cache_dtype)
    args = _attn_inputs(one_chip, L) + pools + [
        _spec(one_chip, (SLOTS, n_pages), jnp.int32),
        _spec(one_chip, (SLOTS, L), jnp.int32),
        _spec(one_chip, (SLOTS,), jnp.int32),
        _spec(one_chip, (), jnp.int32)]

    def fn(q, kn, vn, kp, vp, pp, table, pos, lens, layer, *sc):
        ks, vs = sc if int8 else (None, None)
        return chunk_attention_paged_pallas(
            q, kn, vn, kp, ks, vp, vs, pp, table, pos, lens, layer,
            tile=tile, interpret=False)

    _compile(fn, *args, *(scales if int8 else []))


def _bench_trace():
    """The benchmark's trace reduction (``bench/harness/trace.py``), which
    finds a kernel in a device trace by its op's name."""
    path = Path(__file__).resolve().parents[1] / "bench" / "harness" / \
        "trace.py"
    spec = importlib.util.spec_from_file_location("bench_harness_trace", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod       # its dataclass looks itself up there
    spec.loader.exec_module(mod)
    return mod


def _kernel_ops(compiled):
    return [ln.strip() for ln in compiled.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in ln]


def _instruction(op: str) -> str:
    """``%name.3`` of ``[ROOT ]%name.3 = type custom-call(...)``."""
    return op.split(" = ", 1)[0].removeprefix("ROOT ").lstrip("%")


def test_kernels_carry_their_names(one_chip):
    """The compiled custom calls are named ``ternary_matmul`` and
    ``chunk_attention`` (ring and paged), so a device trace names them: the
    benchmark finds chunk attention by that name, with its signature
    fallback ruled out (the op's target hidden from it)."""
    trace = _bench_trace()
    d, n = 1536, 8960
    ternary = _compile(lambda x, t1, t2, a: tm_ops._pallas(
        x, t1, t2, a, G, interpret=False),
        _spec(one_chip, (8, d), jnp.bfloat16),
        _spec(one_chip, (n, d // 4), jnp.uint8),
        _spec(one_chip, (n, d // 4), jnp.uint8),
        _spec(one_chip, (n, d // G, 2), jnp.float32))
    [op] = _kernel_ops(ternary)
    assert _instruction(op).startswith("ternary_matmul")
    assert trace.kernel_of(op) == "ternary_matmul"

    L = 32
    ring = _storage(one_chip, SLOTS, CAP, jnp.bfloat16)[0] + [
        _spec(one_chip, (SLOTS, L), jnp.int32),
        _spec(one_chip, (SLOTS,), jnp.int32),
        _spec(one_chip, (), jnp.int32)]
    tile = lane_tile(CAP, _select_tile(CAP, L))
    page, n_pages = 16, CAP // 16
    paged = _storage(one_chip, _pool_pages(n_pages), page,
                     jnp.bfloat16)[0] + [
        _spec(one_chip, (SLOTS, n_pages), jnp.int32),
        _spec(one_chip, (SLOTS, L), jnp.int32),
        _spec(one_chip, (SLOTS,), jnp.int32),
        _spec(one_chip, (), jnp.int32)]
    compiled = [
        _compile(lambda q, kn, vn, kc, vc, pb, pos, lens, layer:
                 chunk_attention_pallas(q, kn, vn, kc, None, vc, None, pb,
                                        pos, lens, layer, tile=tile,
                                        interpret=False),
                 *_attn_inputs(one_chip, L), *ring),
        _compile(lambda q, kn, vn, kp, vp, pp, table, pos, lens, layer:
                 chunk_attention_paged_pallas(
                     q, kn, vn, kp, None, vp, None, pp, table, pos, lens,
                     layer, tile=lane_tile(page, paged_tile(page, L)),
                     interpret=False),
                 *_attn_inputs(one_chip, L), *paged)]
    for c in compiled:
        [op] = _kernel_ops(c)
        assert _instruction(op).startswith("chunk_attention")
        hidden = op.replace("tpu_custom_call", "hidden_target")
        assert trace.kernel_of(hidden) == "chunk_attention"


# ops that copy, fill, slice or relay out a buffer; none may produce a
# ring-shaped array (a whole stacked leaf, or one layer's (B, cap, ...))
_RING_OPS = ("broadcast", "copy", "dynamic-slice", "dynamic-update-slice",
             "transpose")


def _ring_shaped_ops(compiled, b: int, cap: int):
    """(opcode, result type) of every HLO instruction, fused ones included,
    of a kind in ``_RING_OPS`` whose result is ring-shaped."""
    ring = re.compile(r"\[(?:%d,)?%d,%d\b" % (LAYERS, b, cap))
    found = []
    for ln in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (\S+?)(?:\{[^}]*\})? "
                     r"([a-z-]+)\(", ln)
        if m and m.group(2) in _RING_OPS and ring.search(m.group(1)):
            found.append((m.group(2), m.group(1)))
    return found


def test_chat_programs_keep_the_ring_in_place(one_chip, monkeypatch):
    """The chat engine's programs at qwen2-1.5b widths (64 slots × 3072
    ring slots, bf16 ring, two layers): a padded prefill chunk of 32 and
    the fused decode loop of 2 steps. The layer scan carries the stacked
    ring, the kernel reads it at the layer index and each step scatters
    only its own tokens, so no ring-sized fill, copy, slice or relayout
    exists and the temporaries stay far below one stacked ring leaf."""
    from repro import configs
    from repro.models import init_decode_state, init_params, prefill_chunk
    from repro.serving.engine import _decode_loop

    b, cap, chunk = 64, 3072, 32
    cfg = configs.get_config("qwen2-1.5b").scaled(
        n_layers=LAYERS, param_dtype="bfloat16",
        activation_dtype="bfloat16", remat="none")
    spec = lambda tree: jax.tree.map(
        lambda a: _spec(one_chip, a.shape, a.dtype), tree)
    params = spec(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))))
    state = spec(jax.eval_shape(lambda: init_decode_state(cfg, b, cap)))
    rows = lambda dtype, *shape: _spec(one_chip, (b,) + shape, dtype)
    # the program takes its TPU branch (Pallas kernels) from the platform
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    prefill = jax.jit(
        lambda p, s, t, n: prefill_chunk(p, cfg, s, {"tokens": t}, n),
        donate_argnums=1).lower(
            params, state, rows(jnp.int32, chunk), rows(jnp.int32)).compile()
    decode = jax.jit(functools.partial(
        _decode_loop, cfg=cfg, n_steps=2, use_mask=False),
        donate_argnums=1).lower(
            params, state, rows(jnp.int32), rows(jnp.float32),
            rows(jnp.bool_), rows(jnp.uint32), rows(jnp.int32),
            rows(jnp.int32), rows(jnp.float32), rows(jnp.int32, 1),
            rows(jnp.int32)).compile()

    leaf = state["blocks"]["b0"]["k"]
    leaf_bytes = leaf.size * leaf.dtype.itemsize
    for name, compiled in (("prefill", prefill), ("decode", decode)):
        assert "tpu_custom_call" in compiled.as_text(), name
        assert _ring_shaped_ops(compiled, b, cap) == [], name
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < leaf_bytes // 4, (name, temp, leaf_bytes)


# deepseek-v2-lite: (n, d) of the routed experts' gate/up and down, and its
# latent ring: 48 slots x 3072, 16 heads, 512 + 64 lanes, 26 scanned layers
EXPERTS, N_EXPERTS, TOP_K = [(1408, 2048), (2048, 1408)], 64, 6
MLA_SLOTS, MLA_HEADS, LATENT, ROPE = 48, 16, 512, 64


@pytest.mark.parametrize("n,d", EXPERTS)
@pytest.mark.parametrize("tokens", [48, 48 * 32])   # a decode step, a chunk
def test_expert_matmul_compiles(one_chip, monkeypatch, n, d, tokens):
    """The grouped expert kernel at its real stack and worst-case tile
    count, reading the planes in their stored layout (no relayout of the
    stack), named so that the trace reader's ternary mark misses it."""
    from repro.kernels.expert_matmul import TILE_ROWS, expert_matmul, n_tiles

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    tiles = n_tiles(tokens * TOP_K, N_EXPERTS)
    compiled = _compile(
        lambda x, t1, t2, a, te, nl: expert_matmul(
            x, t1, t2, a, te, nl, group_size=G, backend="pallas"),
        _spec(one_chip, (tiles * TILE_ROWS, d), jnp.bfloat16),
        _spec(one_chip, (N_EXPERTS, n, d // 4), jnp.uint8),
        _spec(one_chip, (N_EXPERTS, n, d // 4), jnp.uint8),
        _spec(one_chip, (N_EXPERTS, n, d // G, 2), jnp.float32),
        _spec(one_chip, (tiles,), jnp.int32),
        _spec(one_chip, (1,), jnp.int32))
    [op] = _kernel_ops(compiled)
    assert _instruction(op).startswith("expert_matmul")
    assert "ternary" not in op.lower()
    moved = [ln for ln in compiled.as_text().splitlines()
             if re.search(r" (copy|transpose)\(", ln)
             and re.search(r"u8\[%d," % N_EXPERTS, ln)]
    assert not moved, moved[:2]


@pytest.mark.parametrize("L", [1, 32])
def test_latent_attention_compiles(one_chip, L):
    from repro.kernels.chunk_attention.latent import (latent_attention_pallas,
                                                      latent_tile)

    c, cap = LATENT + ROPE, 3072
    compiled = _compile(
        lambda q, kn, ring, pb, pos, lens, layer: latent_attention_pallas(
            q, kn, ring, pb, pos, lens, layer, scale=0.1147, v_dim=LATENT,
            tile=latent_tile(cap, L, True), interpret=False),
        _spec(one_chip, (MLA_SLOTS, L, MLA_HEADS, c), jnp.bfloat16),
        _spec(one_chip, (MLA_SLOTS, L, c), jnp.bfloat16),
        _spec(one_chip, (26, MLA_SLOTS, cap, c), jnp.bfloat16),
        _spec(one_chip, (26, MLA_SLOTS, cap), jnp.int32),
        _spec(one_chip, (MLA_SLOTS, L), jnp.int32),
        _spec(one_chip, (MLA_SLOTS,), jnp.int32),
        _spec(one_chip, (), jnp.int32))
    [op] = _kernel_ops(compiled)
    assert _instruction(op).startswith("latent_attention")


def test_reasoning_programs_keep_the_latent_ring_in_place(one_chip,
                                                          monkeypatch):
    """deepseek-v2-lite's engine programs at published widths (48 slots x
    3072 latent ring slots, the dense layer and two MoE layers, trit-plane
    weights): a padded prefill chunk of 32 and the fused decode loop of 2
    steps run both new kernels, and neither copies, fills or slices the
    stacked latent ring."""
    from repro import configs
    from repro.core.quantize_model import default_predicate, quantize_kernel
    from repro.models import init_decode_state, init_params, prefill_chunk
    from repro.serving.engine import _decode_loop

    b, cap, chunk = MLA_SLOTS, 3072, 32
    cfg = configs.get_config("deepseek-v2-lite").scaled(
        n_layers=LAYERS + 1, param_dtype="bfloat16",
        activation_dtype="bfloat16", remat="none")
    qcfg = ptqtp.PTQTPConfig(group_size=G)

    def planes(path, leaf):       # the quantizer's choice, as shapes only
        name = "".join(f"/{k.key}" for k in path)
        if default_predicate(name, np.broadcast_to(np.int8(0), leaf.shape),
                             G):
            return jax.eval_shape(lambda: quantize_kernel(
                jnp.zeros(leaf.shape, leaf.dtype), qcfg))
        return leaf

    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    shapes = jax.tree_util.tree_map_with_path(planes, shapes)
    spec = lambda tree: jax.tree.map(
        lambda a: _spec(one_chip, a.shape, a.dtype), tree)
    params = spec(shapes)
    state = spec(jax.eval_shape(lambda: init_decode_state(cfg, b, cap)))
    rows = lambda dtype, *shape: _spec(one_chip, (b,) + shape, dtype)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    prefill = jax.jit(
        lambda p, s, t, n: prefill_chunk(p, cfg, s, {"tokens": t}, n),
        donate_argnums=1).lower(
            params, state, rows(jnp.int32, chunk), rows(jnp.int32)).compile()
    decode = jax.jit(functools.partial(
        _decode_loop, cfg=cfg, n_steps=2, use_mask=False),
        donate_argnums=1).lower(
            params, state, rows(jnp.int32), rows(jnp.float32),
            rows(jnp.bool_), rows(jnp.uint32), rows(jnp.int32),
            rows(jnp.int32), rows(jnp.float32), rows(jnp.int32, 1),
            rows(jnp.int32)).compile()

    leaf = state["blocks"]["b0"]["latent"]
    leaf_bytes = leaf.size * leaf.dtype.itemsize
    for name, compiled in (("prefill", prefill), ("decode", decode)):
        names = {_instruction(op).split(".")[0]
                 for op in _kernel_ops(compiled)}
        assert {"expert_matmul", "latent_attention",
                "ternary_matmul"} <= names, (name, names)
        assert _ring_shaped_ops(compiled, b, cap) == [], name
        # the routed rows' buffers (a 48 x 32 chunk: 640 tiles of 16 rows)
        # are the largest temporaries, below one stacked ring leaf
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < leaf_bytes, (name, temp, leaf_bytes)


def test_quantizer_fits_hbm_at_lm_head(one_chip):
    """The trit search over lm_head's 151,936 × 12 group-rows: the (R, G, 9)
    error tensor would be 8.4 GB if materialized; the compiled program must
    keep the whole search well inside one chip's HBM."""
    cfg = ptqtp.PTQTPConfig(group_size=G, t_max=20)
    compiled = ptqtp._quantize_grouped.lower(
        _spec(one_chip, (151936 * 1536 // G, G), jnp.float32),
        group_size=G, t_max=cfg.t_max, eps=cfg.eps,
        lambda_init=cfg.lambda_init, lambda_max=cfg.lambda_max,
        cond_bound=cfg.cond_bound, use_search_kernel=False).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES // 2, total
