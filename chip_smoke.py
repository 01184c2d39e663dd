"""Chip smoke test: quantize and serve full-width qwen2-1.5b on one TPU.

``python3 chip_smoke.py [--seed N]``

One process drives the system's main path through the entry points a user
calls, at the published widths of ``configs/qwen2_1_5b.py`` (28 layers,
d_model 1536, 12/2 heads, d_ff 8960, vocab 151,936, bf16) with random
weights made from ``--seed``:

1. ``repro.launch.quantize.main(--config full)`` writes a trit-plane
   artifact (every linear layer quantized, lm_head included);
2. ``repro.launch.serve.main(--artifact ... --warmup)`` boots it and serves
   a batch of requests — it exits nonzero if any request errors;
3. two of the same requests go over loopback HTTP (unary and SSE) through
   ``EngineDriver`` + ``ThreadedHttpServer``; their tokens must equal the
   in-process tokens at temperature 0;
4. the Pallas kernels are compared with the XLA paths, run at HIGHEST
   matmul precision, on the chip: ``ternary_matmul`` pallas vs grouped on
   the artifact's packed planes, ``chunk_attention`` (ring and paged)
   pallas vs stream, each under its own limit on the relative L2 error;
   and at deepseek-v2-lite's published widths (64 experts of 1408 at d
   2048, 16 heads over a 512 + 64-lane latent), ``expert_matmul`` pallas
   vs its XLA path on a decode step's routed rows and ``latent_attention``
   pallas vs its materialized XLA form.

Each phase prints its wall seconds with compile seconds kept apart, and the
run prints the resolved kernel backends, finish reasons, token counts,
parity errors and the device's peak memory. Any failure exits nonzero;
without a TPU the script exits nonzero before doing any work. The last
line of a passing run is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.

The compiled programs go to JAX's persistent cache
(``repro.runtime.compile_cache``), so a second run compiles less.
"""

from __future__ import annotations

import argparse
import functools
import json
import shutil
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "qwen2-1.5b"
WORK = ROOT / ".chip_smoke"           # listed in .gitignore
SERVE = dict(requests=8, max_new=32, slots=8, capacity=2048, prefill_chunk=16)
# Parity limits on ||pallas - xla||_2 / ||xla||_2, the XLA path run at
# HIGHEST matmul precision as the reference. The Pallas ternary kernel
# feeds the MXU α-scaled weights rounded to the activation dtype (bf16,
# relative step 2^-8), where the XLA path keeps trits exact and applies α
# in f32; the attention kernel's f32 dots run at the MXU's default
# precision. On a v5e over seeds 0-2 the ternary kernel reads 1.59e-3 to
# 1.72e-3 and attention 2.26e-3 to 3.29e-3 (PERF.md); each limit sits
# more than twice above its highest reading, where a wrong group, trit
# field, tile or mask reads O(1).
PARITY_LIMITS = {"ternary_matmul": 5e-3, "chunk_attention": 7.5e-3,
                 # their siblings' numerics (bf16 Ŵ to the MXU; f32 dots at
                 # the MXU's default precision), so their siblings' limits
                 "expert_matmul": 5e-3, "latent_attention": 7.5e-3}
# deepseek-v2-lite: experts, top-k, (n, d) of gate/up and down; latent heads,
# latent and rope lanes (stored padded to 640)
EXPERTS, TOP_K, EXPERT_SHAPES = 64, 6, ((1408, 2048), (2048, 1408))
MLA_HEADS, LATENT, ROPE, LATENT_LANES = 16, 512, 64, 640
# chunk lengths of the attention parity: decode, the served prefill chunk,
# and a chunk whose default tile (32) the compiled kernel rounds up to 128
PARITY_CHUNKS = (1, SERVE["prefill_chunk"], 256)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class Phases:
    """Wall seconds per phase, with the seconds of XLA's backend compiles
    (the program's ``serving_compile*`` counters, one process-wide
    monitor; a persistent-cache hit counts with its read) kept apart;
    tracing and lowering nest across jit levels, so they are left in
    "other" rather than counted twice."""

    def __init__(self, compiles):
        self.compiles = compiles
        self.rows = []

    def run(self, name, fn, *args):
        c = self.compiles
        n0, c0, h0, t0 = c.count, c.seconds, c.hits, time.perf_counter()
        out = fn(*args)
        wall = time.perf_counter() - t0
        comp = c.seconds - c0
        self.rows.append((name, wall, comp))
        log(f"phase {name}: {wall:.2f}s wall = {comp:.2f}s compile + "
            f"{wall - comp:.2f}s other ({c.count - n0} compiles, "
            f"{c.hits - h0} of them persistent-cache hits)")
        return out


def quantize_phase(art_dir: Path, seed: int):
    from repro.launch import quantize

    quantize.main(["--arch", ARCH, "--config", "full", "--seed", str(seed),
                   "--out", str(art_dir), "--overwrite", "--no-resume"])


def serve_phase(art_dir: Path):
    from repro.launch import serve as serve_mod

    try:
        results = serve_mod.main([
            "--artifact", str(art_dir), "--warmup",
            "--requests", str(SERVE["requests"]),
            "--max-new", str(SERVE["max_new"]),
            "--slots", str(SERVE["slots"]),
            "--capacity", str(SERVE["capacity"]),
            "--prefill-chunk", str(SERVE["prefill_chunk"])])
    except SystemExit as e:
        raise RuntimeError(f"serve.main exited: {e.code}") from e
    for r in results:
        log(f"request {r.uid}: finish={r.finish_reason} "
            f"tokens={len(r.tokens)}")
    bad = [r.uid for r in results
           if r.finish_reason not in ("stop", "length") or not r.tokens]
    if len(results) != SERVE["requests"] or bad:
        raise RuntimeError(f"requests not served cleanly: {bad}")
    return results


def _post(base, prompt, max_new, seed, stream):
    import urllib.request

    body = json.dumps({"prompt": list(prompt), "stream": stream,
                       "max_new_tokens": max_new, "seed": seed}).encode()
    req = urllib.request.Request(base + "/v1/completions", data=body,
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=600) as resp:
        if not stream:
            return json.loads(resp.read())["tokens"]
        tokens = []
        for raw in resp:
            line = raw.decode().strip()
            if line.startswith("data: ") and line != "data: [DONE]":
                ev = json.loads(line[len("data: "):])
                if "token" in ev:
                    tokens.append(ev["token"])
        return tokens


def http_phase(art_dir: Path, results):
    from repro.artifacts import load_artifact, load_model_config
    from repro.core.quantize_model import QuantizedKernel
    from repro.data.tokenizer import ByteTokenizer
    from repro.launch.serve import PROMPTS
    from repro.serving import EngineConfig, ServingEngine
    from repro.serving.frontend import EngineDriver, ThreadedHttpServer

    params, manifest = load_artifact(art_dir)
    cfg = load_model_config(manifest)
    lm_head_q = isinstance(params["lm_head"]["kernel"], QuantizedKernel)
    log(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"heads {cfg.n_heads}/{cfg.n_kv_heads}, d_ff {cfg.d_ff}, "
        f"vocab {cfg.vocab_size}, {cfg.param_dtype}; lm_head quantized: "
        f"{lm_head_q}; {manifest['stats']['n_quantized']} quantized kernels")
    if (cfg.n_layers, cfg.d_model, cfg.vocab_size, lm_head_q) \
            != (28, 1536, 151936, True):
        raise RuntimeError("artifact is not full-width qwen2-1.5b with a "
                           "quantized lm_head")
    engine = ServingEngine(params, cfg, EngineConfig(
        max_slots=SERVE["slots"], capacity=SERVE["capacity"],
        prefill_chunk=SERVE["prefill_chunk"]))
    tok = ByteTokenizer()
    driver = EngineDriver(engine).start()
    srv = ThreadedHttpServer(driver).start()
    try:
        base = f"http://{srv.host}:{srv.port}"
        for i, stream in ((0, False), (1, True)):
            prompt = tok.encode(PROMPTS[i % len(PROMPTS)], eos=False)
            got = _post(base, prompt, SERVE["max_new"], i, stream)
            want = list(results[i].tokens)
            kind = "sse" if stream else "unary"
            log(f"http {kind} request {i}: {len(got)} tokens, equal to "
                f"in-process: {got == want}")
            if got != want:
                raise RuntimeError(f"http {kind} tokens differ from the "
                                   f"in-process tokens: {got} vs {want}")
    finally:
        srv.stop()
        driver.drain(timeout=300.0)
        driver.close()
    return params


def _rel_err(got, want) -> float:
    """||got - want||_2 / ||want||_2; inf unless both are finite."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        return float("inf")
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


def parity_phase(params, seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.chunk_attention import (chunk_attention,
                                               chunk_attention_paged)
    from repro.kernels.chunk_attention.ref import to_storage
    from repro.kernels.ternary_matmul.ops import ternary_matmul

    def versus_xla(op, xla_backend, *args):
        got = op(*args, backend="pallas")
        with jax.default_matmul_precision("highest"):
            want = op(*args, backend=xla_backend)
        return _rel_err(got, want)

    def matmul(x, qk, backend):
        return ternary_matmul(x, qk.t1p, qk.t2p, qk.alpha,
                              group_size=qk.group_size, backend=backend)

    def stacked(k, ks, v, vs, pos):
        """Two-layer ring storage holding the case at layer 1; layer 0 has
        other values at the same positions, so reading it shows."""
        def two(a, other):
            return None if a is None else jnp.stack([other, a])
        return to_storage(two(k, -k), two(ks, None if ks is None else 2 * ks),
                          two(v, -v), two(vs, None if vs is None else 2 * vs),
                          two(pos, pos))

    ring_op = functools.partial(chunk_attention, layer=1)
    paged_op = functools.partial(chunk_attention_paged, layer=1)
    rng = np.random.default_rng(seed)
    errs = {}
    mlp = params["blocks"]["b0"]["mlp"]
    for name, qk in (("lm_head", params["lm_head"]["kernel"]),
                     ("mlp_wo", jax.tree.map(lambda a: a[0],
                                             mlp["wo"]["kernel"]))):
        for m in (SERVE["slots"], 256):
            x = jnp.asarray(rng.standard_normal((m, qk.d_in)), jnp.bfloat16)
            errs[("ternary_matmul", f"{name} m={m}")] = versus_xla(
                lambda x, backend: matmul(x, qk, backend), "grouped", x)

    b, cap, kv, g, hd, ps = SERVE["slots"], SERVE["capacity"], 2, 6, 128, 16
    for L in PARITY_CHUNKS:
        for int8 in (False, True):
            q = jnp.asarray(rng.standard_normal((b, L, kv, g, hd)),
                            jnp.float32)
            kn, vn = (jnp.asarray(rng.standard_normal((b, L, kv, hd)),
                                  jnp.float32) for _ in range(2))
            if int8:
                kc, vc = (jnp.asarray(rng.integers(-127, 128,
                                                   (b, cap, kv, hd)), jnp.int8)
                          for _ in range(2))
                ks, vs = (jnp.asarray(rng.uniform(0.005, 0.02, (b, cap, kv)),
                                      jnp.float32) for _ in range(2))
            else:
                kc, vc = (jnp.asarray(rng.standard_normal((b, cap, kv, hd)),
                                      jnp.bfloat16) for _ in range(2))
                ks = vs = None
            # row r has written positions [0, pos0[r]) into its ring
            pos0 = rng.integers(cap // 2, 2 * cap, (b,))
            pb = np.full((b, cap), -1, np.int32)
            for r, p in enumerate(pos0):
                written = np.arange(max(0, p - cap), p)
                pb[r, written % cap] = written
            positions = jnp.asarray(pos0[:, None] + np.arange(L), jnp.int32)
            lengths = jnp.full((b,), L, jnp.int32)
            args = (q, kn, vn, *stacked(kc, ks, vc, vs, jnp.asarray(pb)),
                    positions, lengths)
            tag = f"L={L} {'int8' if int8 else 'bf16'}"
            errs[("chunk_attention", f"ring {tag}")] = versus_xla(
                ring_op, "stream", *args)
            # the same ring as pages in a shuffled pool (page 0 = null page,
            # pages rounded up to a multiple of 8 as the engine allocates)
            n_pages = cap // ps
            perm = rng.permutation(b * n_pages) + 1
            table = jnp.asarray(perm.reshape(b, n_pages), jnp.int32)
            n_phys = -(-(b * n_pages + 1) // 8) * 8

            def pool(a):
                if a is None:
                    return None
                pages = a.reshape((b * n_pages, ps) + a.shape[2:])
                out = jnp.zeros((n_phys,) + pages.shape[1:], a.dtype)
                return out.at[perm].set(pages)
            pos_pool = pool(jnp.asarray(pb)).at[0].set(-1)
            pargs = (q, kn, vn, *stacked(pool(kc), pool(ks), pool(vc),
                                         pool(vs), pos_pool),
                     table, positions, lengths)
            errs[("chunk_attention", f"paged {tag}")] = versus_xla(
                paged_op, "stream", *pargs)
    errs.update(_deepseek_parity(rng, versus_xla))
    for (op, case), v in errs.items():
        log(f"parity {op} {case}: ||pallas-xla||/||xla|| = {v!r} "
            f"(limit {PARITY_LIMITS[op]:g})")
    bad = [k for k, v in errs.items() if not v <= PARITY_LIMITS[k[0]]]
    if bad:
        raise RuntimeError(f"parity beyond its limit: {bad}")


def _deepseek_parity(rng, versus_xla):
    """The grouped expert kernel and latent attention against their XLA
    paths at deepseek-v2-lite's widths, on random inputs."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.packing import pack_trits
    from repro.kernels.chunk_attention import latent_attention
    from repro.kernels.expert_matmul import TILE_ROWS, expert_matmul

    errs = {}
    # a decode step of the served slots: 6 experts per token, sorted by
    # expert into tiles of TILE_ROWS, two dead tiles after the live ones
    experts = rng.integers(0, EXPERTS, SERVE["slots"] * TOP_K)
    counts = np.bincount(experts, minlength=EXPERTS)
    tiles = -(-counts // TILE_ROWS)
    live = int(tiles.sum())
    te = np.repeat(np.arange(EXPERTS), tiles)
    te = jnp.asarray(np.concatenate([te, [te[-1]] * 2]), jnp.int32)
    first = (np.cumsum(tiles) - tiles) * TILE_ROWS
    rows = np.concatenate([first[e] + np.arange(c)
                           for e, c in enumerate(counts) if c])
    for n, d in EXPERT_SHAPES:
        # a two-layer stack read at layer 1; layer 0 holds the experts in
        # reverse order, so reading it shows
        two = lambda a: jnp.stack([a[::-1], a])
        planes = [two(jnp.stack([pack_trits(jnp.asarray(
            rng.integers(-1, 2, (n, d)), jnp.int8)) for _ in range(EXPERTS)]))
            for _ in range(2)]
        alpha = two(jnp.asarray(
            rng.uniform(0.01, 0.03, (EXPERTS, n, d // 128, 2)), jnp.float32))
        x = np.zeros(((live + 2) * TILE_ROWS, d), np.float32)
        x[rows] = rng.standard_normal((len(rows), d))
        x = jnp.asarray(x, jnp.bfloat16)

        def op(x, backend):
            y = expert_matmul(x, *planes, alpha, te, jnp.asarray([live]), 1,
                              backend=backend)
            return y[:live * TILE_ROWS]                 # live tiles only
        errs[("expert_matmul", f"{n}x{d} rows={len(rows)}")] = versus_xla(
            op, "xla", x)

    b, cap = SERVE["slots"], SERVE["capacity"]
    for L in PARITY_CHUNKS:
        pad = LATENT_LANES - LATENT - ROPE
        lanes = lambda *s: jnp.pad(jnp.asarray(rng.standard_normal(
            s + (LATENT + ROPE,)), jnp.float32), [(0, 0)] * len(s)
            + [(0, pad)])
        ring = jnp.stack([-lanes(b, cap), lanes(b, cap)]).astype(jnp.bfloat16)
        pos0 = rng.integers(cap // 2, 2 * cap, (b,))
        pb = np.full((b, cap), -1, np.int32)
        for r, p in enumerate(pos0):
            written = np.arange(max(0, p - cap), p)
            pb[r, written % cap] = written
        args = (lanes(b, L, MLA_HEADS), lanes(b, L).astype(jnp.bfloat16),
                ring, jnp.asarray(np.stack([pb, pb])),
                jnp.asarray(pos0[:, None] + np.arange(L), jnp.int32),
                jnp.full((b,), L, jnp.int32))
        errs[("latent_attention", f"L={L}")] = versus_xla(
            functools.partial(latent_attention, scale=0.11472, v_dim=LATENT,
                              layer=1), "xla", *args)
    return errs


def smoke(seed: int) -> None:
    """The phases, in order; raises on the first failure."""
    import jax

    from repro.kernels.chunk_attention import resolve_chunk_backend
    from repro.kernels.ternary_matmul.ops import resolve_backend
    from repro.runtime.compile_cache import enable_compile_cache
    from repro.serving.observability import compile_monitor

    log(f"compile cache: {enable_compile_cache()}")
    log(f"backends: ternary_matmul auto -> {resolve_backend('auto')}, "
        f"chunk_attention auto -> {resolve_chunk_backend('auto')}, "
        f"pallas interpret mode: {jax.default_backend() != 'tpu'}")
    phases = Phases(compile_monitor())
    art_dir = WORK / "artifact"
    t0 = time.perf_counter()
    try:
        phases.run("quantize", quantize_phase, art_dir, seed)
        results = phases.run("serve", serve_phase, art_dir)
        params = phases.run("http", http_phase, art_dir, results)
        phases.run("parity", parity_phase, params, seed)
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    total = time.perf_counter() - t0
    comp = sum(c for _, _, c in phases.rows)
    log(f"total: {total:.2f}s wall, {comp:.2f}s compile")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and parity inputs")
    args = ap.parse_args(argv)

    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        log(f"FAILED: JAX found no device: {e}")
        return 1
    dev = devices[0]
    if dev.platform != "tpu":
        log(f"FAILED: JAX found platform {dev.platform!r} "
            f"({dev.device_kind}), not 'tpu'")
        return 1
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    sys.path.insert(0, str(ROOT / "src"))
    try:
        smoke(args.seed)
    except Exception:
        traceback.print_exc()
        log("FAILED")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
