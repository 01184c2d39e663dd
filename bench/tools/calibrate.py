"""Readings that set a cell's correctness limit: the program and its
float8 control, on many seeds, in one process.

    python3 bench/tools/calibrate.py --workload <cell> --seeds 1,2,3 \
        --seconds 10

Each seed is a whole run of the cell as ``bench/run.py`` makes it (its own
weights, traffic and window) whose sample of served tokens is compared
with the reference in float32 — the program's reading — and with the
reference in float8 — the control's reading — each judged by the run's
own rule. One JSON line per seed; the limit is set by hand between the
program's largest reading and the control's smallest (see PERF.md). Exits
nonzero where a control reads correct. Not part of the benchmark's own
runs.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(ROOT / "src"))
    from harness import device, session, spec

    cell = spec.load_cell(ROOT, args.workload)
    devices = device.require_chips(cell.chips)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_enable_compilation_cache", True)
    control_passed = []
    for seed in (int(s) for s in args.seeds.split(",")):
        in_use = devices[0].memory_stats().get("bytes_in_use")
        line = session.run_cell(cell, seed, args.seconds, False,
                                time.perf_counter(), devices=devices,
                                control=True)
        if line["control"]["correct"]:
            control_passed.append(seed)
        print(json.dumps({
            "seed": seed, "correct": line["correct"],
            "control_correct": line["control"]["correct"],
            "program": line["control"]["program"],
            "control": line["control"]["control"],
            "tokens_compared": line["checks"]["tokens_compared"]["value"],
            "bytes_in_use_before": in_use,
            "metrics": {k: v["value"] for k, v in line["metrics"].items()}}),
            flush=True)
    if control_passed:
        print(f"the control read correct on seeds {control_passed}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
