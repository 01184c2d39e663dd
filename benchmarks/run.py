"""Benchmark harness: one function per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--only NAME]``

Prints ``bench,key,value`` CSV lines; each bench also persists JSON to
benchmarks/results/<name>.json (consumed by EXPERIMENTS.md).
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks import (bench_artifacts, bench_condition, bench_decode,
                        bench_groupwise, bench_http, bench_iterations,
                        bench_latency, bench_memory, bench_paged_kv,
                        bench_perplexity, bench_prefill, bench_recovery,
                        bench_roofline, bench_runtime, bench_serving_api,
                        bench_tolerance)
from benchmarks.common import RESULTS

SUITES = {
    "perplexity": bench_perplexity.run,    # Table 1/2/9
    "runtime": bench_runtime.run,          # Fig. 1(b), App. A.2
    "memory": bench_memory.run,            # Table 4, Eq. 9-13
    "latency": bench_latency.run,          # Tables 5/6
    "decode": bench_decode.run,            # decode fast path (tok/s trajectory)
    "prefill": bench_prefill.run,          # bucketed/chunked admission (TTFT)
    "artifacts": bench_artifacts.run,      # quantize-once/serve-many boot
    "serving_api": bench_serving_api.run,  # v1 streaming TTFT + cancel churn
    "paged_kv": bench_paged_kv.run,        # paged pool + COW prefix reuse
    "http": bench_http.run,                # v1.4 wire identity + DRR fairness
    "recovery": bench_recovery.run,        # v1.5 MTTR/availability/replay

    "iterations": bench_iterations.run,    # Fig. 3
    "tolerance": bench_tolerance.run,      # Fig. 4
    "condition": bench_condition.run,      # Table 7
    "groupwise": bench_groupwise.run,      # Table 8
    "roofline": bench_roofline.run,        # §Roofline deliverable
}


def _headline_metrics(payload) -> list:
    """(key, value) pairs worth surfacing for one bench's JSON payload.

    Preference order: explicit ``headline_*`` keys, then ``*speedup*`` keys,
    then the first scalar — so every bench shows *something* without each
    having to opt in.
    """
    if not isinstance(payload, dict):
        return []
    scalars = {k: v for k, v in payload.items()
               if isinstance(v, (int, float)) and not isinstance(v, bool)}
    for picker in (lambda k: k.startswith("headline_"),
                   lambda k: "speedup" in k):
        picked = [(k, v) for k, v in scalars.items() if picker(k)]
        if picked:
            return picked[:3]
    return list(scalars.items())[:1]


def print_summary(out=print) -> None:
    """One table over every benchmarks/results/*.json produced so far."""
    import json

    rows = []
    for p in sorted(RESULTS.glob("*.json"), key=lambda p: p.name.lower()):
        try:
            payload = json.loads(p.read_text())
        except (OSError, json.JSONDecodeError):
            continue
        for key, val in _headline_metrics(payload):
            rows.append((p.stem, key, val))
    if not rows:
        out("(no benchmark results under benchmarks/results/)")
        return
    wn = max(len(r[0]) for r in rows)
    wk = max(len(r[1]) for r in rows)
    out(f"{'bench':<{wn}}  {'metric':<{wk}}  value")
    out("-" * (wn + wk + 12))
    for name, key, val in rows:
        sval = f"{val:.3f}" if isinstance(val, float) else str(val)
        out(f"{name:<{wn}}  {key:<{wk}}  {sval}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=tuple(SUITES), default=None)
    args = ap.parse_args(argv)
    todo = {args.only: SUITES[args.only]} if args.only else SUITES

    failed = []
    for name, fn in todo.items():
        print(f"=== bench:{name} ===", flush=True)
        t0 = time.time()
        try:
            fn(log=lambda s: print(s, flush=True))
            print(f"=== bench:{name} done in {time.time() - t0:.1f}s ===",
                  flush=True)
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            failed.append(name)
    print("=== summary (all recorded results) ===", flush=True)
    print_summary()
    if failed:
        print(f"FAILED: {failed}")
        sys.exit(1)
    print("ALL BENCHMARKS OK")


if __name__ == "__main__":
    main()
