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

import importlib.util
import os
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
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


@pytest.mark.parametrize("cache_dtype", [jnp.bfloat16, jnp.int8])
@pytest.mark.parametrize("L", CHUNKS)
def test_chunk_attention_ring_compiles(one_chip, L, cache_dtype):
    int8 = cache_dtype == jnp.int8
    tile = lane_tile(CAP, _select_tile(CAP, L))
    args = _attn_inputs(one_chip, L) + [
        _spec(one_chip, (SLOTS, CAP, KV, HD), cache_dtype),
        _spec(one_chip, (SLOTS, CAP, KV, HD), cache_dtype),
        _spec(one_chip, (SLOTS, CAP), jnp.int32),
        _spec(one_chip, (SLOTS, L), jnp.int32),
        _spec(one_chip, (SLOTS,), jnp.int32)]
    scales = [_spec(one_chip, (SLOTS, CAP, KV), jnp.float32)] * 2

    def fn(q, kn, vn, kc, vc, pb, pos, lens, *sc):
        ks, vs = sc if int8 else (None, None)
        return chunk_attention_pallas(q, kn, vn, kc, ks, vc, vs, pb, pos,
                                      lens, tile=tile, interpret=False)

    _compile(fn, *args, *(scales if int8 else []))


@pytest.mark.parametrize("cache_dtype", [jnp.bfloat16, jnp.int8])
@pytest.mark.parametrize("L,page", [(1, 16), (64, 16), (256, 16),
                                    (256, 512)])
def test_chunk_attention_paged_compiles(one_chip, L, page, cache_dtype):
    int8 = cache_dtype == jnp.int8
    n_pages = CAP // page
    pool = SLOTS * n_pages + 1                 # + the null page
    tile = lane_tile(page, paged_tile(page, L))
    args = _attn_inputs(one_chip, L) + [
        _spec(one_chip, (pool, page, KV, HD), cache_dtype),
        _spec(one_chip, (pool, page, KV, HD), cache_dtype),
        _spec(one_chip, (pool, page), jnp.int32),
        _spec(one_chip, (SLOTS, n_pages), jnp.int32),
        _spec(one_chip, (SLOTS, L), jnp.int32),
        _spec(one_chip, (SLOTS,), jnp.int32)]
    scales = [_spec(one_chip, (pool, page, KV), jnp.float32)] * 2

    def fn(q, kn, vn, kp, vp, pp, table, pos, lens, *sc):
        ks, vs = sc if int8 else (None, None)
        return chunk_attention_paged_pallas(
            q, kn, vn, kp, ks, vp, vs, pp, table, pos, lens, tile=tile,
            interpret=False)

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
    ring = [_spec(one_chip, (SLOTS, CAP, KV, HD), jnp.bfloat16)] * 2 + [
        _spec(one_chip, (SLOTS, CAP), jnp.int32),
        _spec(one_chip, (SLOTS, L), jnp.int32),
        _spec(one_chip, (SLOTS,), jnp.int32)]
    tile = lane_tile(CAP, _select_tile(CAP, L))
    page, n_pages = 16, CAP // 16
    pool = SLOTS * n_pages + 1
    paged = [_spec(one_chip, (pool, page, KV, HD), jnp.bfloat16)] * 2 + [
        _spec(one_chip, (pool, page), jnp.int32),
        _spec(one_chip, (SLOTS, n_pages), jnp.int32),
        _spec(one_chip, (SLOTS, L), jnp.int32),
        _spec(one_chip, (SLOTS,), jnp.int32)]
    compiled = [
        _compile(lambda q, kn, vn, kc, vc, pb, pos, lens:
                 chunk_attention_pallas(q, kn, vn, kc, None, vc, None, pb,
                                        pos, lens, tile=tile,
                                        interpret=False),
                 *_attn_inputs(one_chip, L), *ring),
        _compile(lambda q, kn, vn, kp, vp, pp, table, pos, lens:
                 chunk_attention_paged_pallas(
                     q, kn, vn, kp, None, vp, None, pp, table, pos, lens,
                     tile=lane_tile(page, paged_tile(page, L)),
                     interpret=False),
                 *_attn_inputs(one_chip, L), *paged)]
    for c in compiled:
        [op] = _kernel_ops(c)
        assert _instruction(op).startswith("chunk_attention")
        hidden = op.replace("tpu_custom_call", "hidden_target")
        assert trace.kernel_of(hidden) == "chunk_attention"


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
