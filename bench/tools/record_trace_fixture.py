"""Record the small TPU trace that the trace reduction's test reads.

    python3 bench/tools/record_trace_fixture.py [--out bench/.out]

On one TPU chip: the program's ternary matmul (d 1536 -> n 8960 at m 64
and m 2048, a qwen2-1.5b MLP projection) and its ring chunk attention
(64 rows x 4096 slots, 2 KV heads of 128, chunks of 1 and 32), reading
layer 1 of a two-layer stacked ring as the layer scan does, each
compiled first and then run twice between the benchmark's two window
marks. Writes ``<out>/tpu_v5e.xplane.pb`` (about 150 KB) and prints its
reduction; copy the file to ``bench/tests/fixtures/``.
"""

from __future__ import annotations

import argparse
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", type=Path, default=BENCH / ".out")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(ROOT / "src"))
    from harness import device, trace

    device.require_chips(1)
    import jax
    import jax.numpy as jnp

    from repro.core.packing import pack_trits
    from repro.kernels.chunk_attention import chunk_attention
    from repro.kernels.chunk_attention.ref import to_storage
    from repro.kernels.ternary_matmul.ops import ternary_matmul

    n, d = 8960, 1536
    t = jax.random.randint(jax.random.PRNGKey(0), (n, d), -1, 2
                           ).astype(jnp.int8)
    t1p, t2p = pack_trits(t), pack_trits(-t)
    alpha = jnp.full((n, d // 128, 2), 0.01, jnp.float32)

    @jax.jit
    def mm(x):
        return ternary_matmul(x, t1p, t2p, alpha, group_size=128,
                              backend="pallas", out_dtype=x.dtype)

    n_layers, b, cap, kv, g, hd = 2, 64, 4096, 2, 6, 128
    ring = jnp.zeros((n_layers, b, cap, kv, hd), jnp.bfloat16)
    kc, _, vc, _, pos = to_storage(
        ring, None, ring, None,
        jnp.tile(jnp.arange(cap, dtype=jnp.int32)[None, None],
                 (n_layers, b, 1)))

    @jax.jit
    def att(q, kn, layer):
        L = q.shape[1]
        positions = jnp.full((b, L), cap, jnp.int32) + jnp.arange(L)[None]
        return chunk_attention(q, kn, kn, kc, None, vc, None, pos, positions,
                               jnp.full((b,), L, jnp.int32), layer=layer,
                               backend="pallas")

    layer = jnp.int32(1)

    xs = [jnp.ones((64, d), jnp.bfloat16), jnp.ones((2048, d), jnp.bfloat16)]
    qs = [(jnp.ones((b, L, kv, g, hd), jnp.float32),
           jnp.ones((b, L, kv, hd), jnp.float32)) for L in (1, 32)]
    for x in xs:
        mm(x).block_until_ready()
    for q, kn in qs:
        att(q, kn, layer).block_until_ready()

    trace_dir = args.out / "fixture_trace"
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(trace_dir))
    time.sleep(0.02)
    with jax.profiler.TraceAnnotation(trace.MARK_OPEN):
        pc_open = time.perf_counter()
    for _ in range(2):
        for x in xs:
            mm(x).block_until_ready()
        time.sleep(0.003)
        for q, kn in qs:
            att(q, kn, layer).block_until_ready()
    with jax.profiler.TraceAnnotation(trace.MARK_CLOSE):
        pc_close = time.perf_counter()
    jax.profiler.stop_trace()

    dest = args.out / "tpu_v5e.xplane.pb"
    shutil.copy(trace.newest_xplane(trace_dir), dest)
    red = trace.reduce(dest, pc_open, pc_close)
    print(f"{dest}: window {red.window_s:.6f} s, busy {red.busy_s:.6f} s, "
          f"kernels {red.kernel_s}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
