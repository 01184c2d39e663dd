"""Find the highest request rate an open-loop cell sustains on the chip.

    python3 bench/tools/knee_sweep.py --workload <cell> --rates 1,2,3 \
        --seconds 30 [--seed N]

One process, one engine: for each rate in turn, the cell's traffic at that
rate (with ``--warm`` seconds of arrivals first) for
``--seconds``, then a full drain. For each rate it prints the requests
waiting for a slot (the frontend queue plus the engine's) and the slots
in use, sampled every 5 s of the window, and TTFT/TPOT p50 and p90 of the
requests due in the window. The knee is the highest rate whose waiting
count does not grow over the window; a cell's rate is set from it once,
by hand, in the traffic file.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--warm", type=float, default=None,
                    help="seconds of arrivals before each window "
                         "(default: the traffic file's warm_s)")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(ROOT / "src"))
    from harness import device, load, session, spec, traffic

    cell = spec.load_cell(ROOT, args.workload)
    device.require_chips(cell.chips)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_enable_compilation_cache", True)
    sv = session.serve(cell, args.seed, traced=False)

    def waiting():
        return sv.driver.call(lambda e: len(e.queue)) \
            + sv.driver.stats()["pending"]

    warm = float(cell.traffic["warm_s"] if args.warm is None else args.warm)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        tr = dict(cell.traffic, warm_s=warm,
                  arrivals=dict(cell.traffic["arrivals"], rate_per_s=rate))
        reqs = traffic.schedule(tr, args.seed + i, args.seconds,
                                sv.model_cfg.vocab_size,
                                sv.engine_cfg.max_slots)
        sv.load.records.clear()
        samples, stop = [], threading.Event()
        t_open = load.clock() + warm + 0.5

        def sample():
            load.sleep_until(t_open)
            while not stop.is_set():
                samples.append((waiting(), sv.load.resident()))
                stop.wait(5.0)

        sampler = threading.Thread(target=sample, daemon=True)
        sampler.start()
        t_close = load.run_open_loop(sv.load, reqs, t_open, args.seconds)
        stop.set()
        sampler.join()
        recs = list(sv.load.records)
        load.wait_finished(recs, 600.0)
        ok = [r for r in recs if r.finish in ("length", "stop")
              and len(r.times) > 1 and r.due >= t_open]
        ttft = [r.times[0] - r.due for r in ok]
        tpot = [(r.times[-1] - r.times[0]) / (len(r.times) - 1) for r in ok]
        toks = sum(1 for r in recs for t in r.times if t_open <= t < t_close)
        pct = session.percentile
        print(json.dumps({
            "rate_per_s": rate, "requests": len(recs), "in_window": len(ok),
            "waiting_every_5s": [w for w, _ in samples],
            "slots_in_use_every_5s": [n for _, n in samples],
            "tokens_per_s": toks / args.seconds,
            "ttft_p50_ms": pct(ttft, 50) * 1e3 if ttft else None,
            "ttft_p90_ms": pct(ttft, 90) * 1e3 if ttft else None,
            "tpot_p50_ms": pct(tpot, 50) * 1e3 if tpot else None,
            "tpot_p90_ms": pct(tpot, 90) * 1e3 if tpot else None}),
            flush=True)
    sv.driver.close(timeout=120.0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
