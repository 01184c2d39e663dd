"""On-chip benchmark of the trit-plane serving engine: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the cell
asks for (``BENCHMARK.json``). The run makes its weights and its requests
from ``--seed``, sets up and warms every program, measures for
``--seconds``, checks the served tokens against the configuration's plain
reference, and prints one JSON line last on standard output. With
``--trace 0`` its metrics are the cell's end-to-end metrics; with
``--trace 1`` the per-layer ones, read from a profiler trace of a few
seconds of the window. The numbers that decide ``correct`` are printed
last on standard error, each beside its limit, and again under
``checks``, the result line's last key.

Without a TPU, or with fewer chips than the cell asks for, it exits with
code 3 and prints no result. The compile cache is ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
EXIT_NO_CHIP = 3


def _finite(x):
    """JSON has no inf or NaN: write them as null."""
    if isinstance(x, float) and not math.isfinite(x):
        return None
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [_finite(v) for v in x]
    return x


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(ROOT / "src"))
    from harness import device, session, spec

    cell = spec.load_cell(ROOT, args.workload)
    try:
        devices = device.require_chips(cell.chips)
    except device.NoChip as e:
        print(f"[bench] no chip: {e}", file=sys.stderr, flush=True)
        return EXIT_NO_CHIP
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_enable_compilation_cache", True)
    line = session.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                            T_PROCESS, devices=devices)
    print(json.dumps(_finite(line)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
