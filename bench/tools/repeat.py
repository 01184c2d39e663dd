"""Run one cell several times, each run a process of its own, and print the
spread of every metric.

    python3 bench/tools/repeat.py --workload <cell> --seeds 11,12,13 \
        --seconds 30 [--trace 0] [--sets 2] [--out bench/.out/repeat]

The seeds are run in order, as one set; with ``--sets 2`` the same seeds
are run again as a second set. This process never imports JAX, so each
child has the chip to itself. Every run's standard output and error go to
``<out>/<cell>.<set>.<seed>.{out,err}``. For each set and metric it prints
the median, the quartile spread as a share of the median (Python's
``statistics.quantiles(values, n=4)``) and the same with the run farthest
from the median left out; then the bound that five times the wider spread
would give.
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def spread(values):
    """(q3 - q1) / median, or None with fewer than two values."""
    if len(values) < 2:
        return None
    med = statistics.median(values)
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / abs(med) if med else None


def trimmed(values):
    """The values without the one farthest from their median."""
    if len(values) < 3:
        return list(values)
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return [v for i, v in enumerate(values) if i != far]


def run_one(cell, seed, seconds, trace, out: Path, tag: str):
    t0 = time.time()
    cmd = [sys.executable, str(BENCH / "run.py"), "--workload", cell,
           "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace)]
    with open(out / f"{tag}.out", "w") as fo, open(out / f"{tag}.err",
                                                   "w") as fe:
        rc = subprocess.run(cmd, stdout=fo, stderr=fe, cwd=ROOT).returncode
    lines = (out / f"{tag}.out").read_text().strip().splitlines()
    line = None
    if lines:
        try:
            line = json.loads(lines[-1])
        except json.JSONDecodeError:
            line = None
    info = [ln for ln in (out / f"{tag}.err").read_text().splitlines()
            if ln.startswith("[bench]")]
    print(f"== {tag} rc={rc} {time.time() - t0:.1f}s", flush=True)
    for ln in info[-6:]:
        print("  " + ln[:300], flush=True)
    if line is None:
        tail = (out / f"{tag}.err").read_text()[-1500:]
        print(tail, flush=True)
    else:
        short = {k: v["value"] for k, v in line["metrics"].items()}
        print("  " + json.dumps({"correct": line["correct"],
                                 "attempted": line["attempted"],
                                 "failed": line["failed"],
                                 "metrics": short,
                                 "device": line["device"]}), flush=True)
        if "breakdown" in line:
            print("  " + json.dumps(line["breakdown"])[:3000], flush=True)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--out", type=Path, default=BENCH / ".out" /
                    "repeat")
    args = ap.parse_args(argv)
    args.out.mkdir(parents=True, exist_ok=True)
    seeds = [int(s) for s in args.seeds.split(",")]
    sets = []
    for k in range(args.sets):
        lines = [run_one(args.workload, s, args.seconds, args.trace,
                         args.out, f"{args.workload}.{k}.{s}")
                 for s in seeds]
        sets.append(lines)
    names = sorted({m for ls in sets for ln in ls if ln
                    for m in ln["metrics"]})
    summary = {}
    for m in names:
        rows = []
        for k, ls in enumerate(sets):
            vals = [ln["metrics"][m]["value"] for ln in ls
                    if ln and m in ln["metrics"]
                    and ln["metrics"][m]["value"] is not None]
            rows.append({"set": k, "n": len(vals),
                         "median": statistics.median(vals) if vals else None,
                         "spread": spread(vals),
                         "spread_trimmed": spread(trimmed(vals)),
                         "values": vals})
        widest = max((r["spread"] or 0.0) for r in rows)
        summary[m] = {"sets": rows, "widest_spread": widest,
                      "five_times": 5 * widest}
        print(f"{m}: " + json.dumps(summary[m]), flush=True)
    ok = [ln["correct"] for ls in sets for ln in ls if ln]
    print(json.dumps({"runs": sum(len(ls) for ls in sets),
                      "results": len(ok), "correct": sum(ok)}), flush=True)
    (args.out / f"{args.workload}.summary.json").write_text(
        json.dumps(summary, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
