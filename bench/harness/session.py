"""One run of one cell: set up, measure a window, check, report.

Set-up (``setup_s``, from process start to the window's opening): weights
from the seed on the device, the engine built and every program warmed,
one warm request through the driver, and for open-loop traffic the
``warm_s`` seconds of arrivals before the window (for a backlog, the time
to occupy every slot). The window then runs for ``--seconds``; nothing
compiles inside it (the count is printed). With ``--trace 1`` the profiler
records a few seconds in the middle of the window and the per-layer
metrics are read from that trace, the engine's spans and counters.

After the window: open-loop requests due in it are waited for (up to
``DRAIN_S``), the device's peak memory is read, the program's state is
freed, and the served tokens are checked against the reference.
"""

from __future__ import annotations

import bisect
import dataclasses
import gc
import math
import shutil
import sys
import threading
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

from harness import correct, device, load, model, traffic, trace, weights, work
from harness.spec import Cell

DRAIN_S = 120.0          # wait for requests due in the window after it
TRACE_S = 5.0            # profiled seconds, in the middle of the window
WARM_PROMPT = 40         # the warm request's prompt tokens
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class CompileClock:
    """XLA compiles (count and seconds) and persistent-cache hits of this
    process; ``get()`` registers the one instance with JAX's monitoring."""

    _instance = None

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self.hits = 0

    @classmethod
    def get(cls) -> "CompileClock":
        if cls._instance is None:
            import jax

            cls._instance = cls()
            jax.monitoring.register_event_duration_secs_listener(
                cls._instance._duration)
            jax.monitoring.register_event_listener(cls._instance._event)
        return cls._instance

    def _duration(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.count += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in [0, 100]) of a non-empty sequence."""
    v = sorted(values)
    return float(v[max(0, math.ceil(q / 100.0 * len(v)) - 1)])


@dataclasses.dataclass
class Run:
    """What a per-layer metric's reader may read."""
    cell: Cell
    max_slots: int
    records: List[load.Record]        # every request of the run
    in_window: List[load.Record]      # open loop: due in the window
    t_open: float
    t_close: float
    counters: Dict[str, Dict[str, float]]   # "open"/"close" engine counters
    spans: List[Any]                  # the engine's TraceRecorder events
    reduction: Optional[trace.Reduction] = None
    work: Optional[Dict[str, work.Tally]] = None
    peaks: Optional[Dict[str, Any]] = None

    def engine_spans(self, name: str, t0: float, t1: float):
        return [e for e in self.spans if e.track == ("engine", 0)
                and e.name == name and t0 <= e.ts <= t1]


def _counters(engine) -> Dict[str, float]:
    return {"tokens_generated": engine.tokens_generated,
            "decode_steps": engine.steps}


def traced_work(mdl: work.Model, run: Run, pc_open: float, pc_close: float,
                peaks) -> Dict[str, work.Tally]:
    """Work the served tokens need in the dispatches the traced window
    holds (each dispatch by the host time it was issued)."""
    out = mdl.tallies()

    def add(tallies):
        for k, v in tallies.items():
            out[k].merge(v)

    prompt_len = {r.uid: len(r.req.prompt) for r in run.records}
    chunks: Dict[tuple, List[work.Row]] = {}
    for e in run.spans:
        if e.name == "prefill_chunk" and e.track[1] in prompt_len \
                and pc_open <= e.ts <= pc_close:
            n, cursor = e.args["tokens"], e.args["cursor"]
            chunks.setdefault((e.ts, e.dur), []).append(work.Row(
                cursor - n, n, int(cursor >= prompt_len[e.track[1]])))
    for rows in chunks.values():
        add(work.dispatch(mdl, [rows], peaks))

    decodes = sorted(run.engine_spans("decode_dispatch", -math.inf,
                                      math.inf), key=lambda e: e.ts)
    ends = [e.ts + e.dur for e in decodes]
    tokens: Dict[int, Dict[int, List[int]]] = {}   # dispatch -> uid -> idx
    for r in run.records:
        for i, t in enumerate(r.times[1:], start=1):
            d = bisect.bisect_right(ends, t) - 1
            if d >= 0:
                tokens.setdefault(d, {}).setdefault(r.uid, []).append(i)
    for d, per_uid in tokens.items():
        if not pc_open <= decodes[d].ts <= pc_close:
            continue
        n_steps = max(len(v) for v in per_uid.values())
        steps = [[work.Row(prompt_len[uid] + idx[0] - 1 + s, 1, 1)
                  for uid, idx in per_uid.items() if s < len(idx)]
                 for s in range(n_steps)]
        add(work.dispatch(mdl, steps, peaks))
    return out


@dataclasses.dataclass
class Serving:
    engine: Any
    driver: Any
    load: load.Load
    model_cfg: Any
    engine_cfg: Any
    compiles: CompileClock


def serve(cell: Cell, seed: int, traced: bool) -> Serving:
    """Set-up: weights from the seed, the engine warmed, the driver
    started, and one warm request served through it."""
    from repro.serving import SamplingParams, ServingEngine
    from repro.serving.frontend import EngineDriver
    from repro.serving.observability import Observability

    c = cell.config
    mcfg, ecfg = model.model_config(c, cell.arch), model.engine_config(c)
    compiles = CompileClock.get()
    params = weights.program_params(mcfg, seed,
                                    c["quantization"]["group_size"],
                                    weights.leaf_rules(cell.arch))
    engine = ServingEngine(params, mcfg, ecfg,
                           observability=Observability(trace=traced))
    del params
    engine.warmup()
    driver = EngineDriver(engine, fairness=model.fairness(c)).start()
    lg = load.Load(driver, lambda req: SamplingParams(
        max_new_tokens=req.max_new, temperature=0.0, seed=req.index))
    warm = traffic.Request(-1, None, np.arange(1, WARM_PROMPT + 1,
                                               dtype=np.int32),
                           ecfg.decode_chunk + 2)
    lg.submit(warm)
    load.wait_finished(lg.records, 600.0)
    if lg.records[0].finish not in ("length", "stop"):
        raise RuntimeError(f"warm request failed: {lg.records[0].finish} "
                           f"{lg.records[0].error}")
    lg.records.clear()
    while not lg.done.empty():
        lg.done.get()
    return Serving(engine, driver, lg, mcfg, ecfg, compiles)


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             t_process: float, *, devices=None, control: bool = False,
             out_dir: Optional[Path] = None) -> Dict[str, Any]:
    """One run; returns the result line (and, with ``control``, the
    control's gaps under ``"control"``). ``devices``: the chips to run on
    (the caller has checked them)."""
    import jax

    c = cell.config
    sv = serve(cell, seed, traced)
    engine, driver, lg, compiles = sv.engine, sv.driver, sv.load, sv.compiles
    mcfg, ecfg = sv.model_cfg, sv.engine_cfg
    del sv

    reqs = traffic.schedule(cell.traffic, seed, seconds, mcfg.vocab_size,
                            ecfg.max_slots)
    kind = cell.traffic["kind"]
    marks: Dict[str, float] = {}
    trace_dir = (out_dir or cell.bench_dir / ".out") / "trace" / cell.name

    def start_trace():
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
        with jax.profiler.TraceAnnotation(trace.MARK_OPEN):
            marks["open"] = load.clock()

    def stop_trace():
        with jax.profiler.TraceAnnotation(trace.MARK_CLOSE):
            marks["close"] = load.clock()
        jax.profiler.stop_trace()

    def profile(t_open):
        """Profile TRACE_S seconds in the middle of the window, on a
        thread of its own so that the load generator keeps time."""
        t_a = t_open + max(0.0, (seconds - TRACE_S) / 2)

        def body():
            load.sleep_until(t_a)
            start_trace()
            load.sleep_until(t_a + min(TRACE_S, seconds))
            stop_trace()

        th = threading.Thread(target=body, name="bench-profiler")
        if traced:
            th.start()
        return th

    counters: Dict[str, Dict[str, float]] = {}
    if kind == "open_loop":
        t_open = load.clock() + cell.traffic["warm_s"]
        pre = [r for r in reqs if r.due_s < 0]
        rest = [r for r in reqs if r.due_s >= 0]
        load.run_open_loop(lg, pre, t_open, 0.0)
        counters["open"] = driver.call(_counters)
        c_open = compiles.count
        prof = profile(t_open)
        t_close = load.run_open_loop(lg, rest, t_open, seconds)
    else:
        nxt = load.fill_backlog(lg, reqs, ecfg.max_slots,
                                int(cell.traffic["backlog"]))
        t_open = load.clock()
        counters["open"] = driver.call(_counters)
        c_open = compiles.count
        prof = profile(t_open)
        t_close = load.run_backlog(lg, reqs, nxt, t_open, seconds)
    counters["close"] = driver.call(_counters)
    if traced:
        prof.join()
    c_window = compiles.count - c_open
    setup_s = t_open - t_process

    in_window = ([r for r in lg.records if r.due is not None
                  and t_open <= r.due < t_close] if kind == "open_loop"
                 else [])
    load.wait_finished(in_window, DRAIN_S)
    t_waited = load.clock()
    driver.close(timeout=120.0)
    dev = device.describe(devices)
    spans = engine.obs.trace.events() if traced else []
    for r in lg.records:
        r.finalize()
    records = lg.records
    del lg, driver, engine
    gc.collect()

    late = load.lateness(in_window)
    admitted = sum(1 for r in records if t_open <= r.t_admit < t_close)
    finished = sum(1 for r in records if r.finish in ("length", "stop")
                   and r.times and t_open <= r.times[-1] < t_close)
    log(f"window {seconds:g} s: {len(records)} requests in the run, "
        f"{len(in_window)} due in the window, {admitted} admitted and "
        f"{finished} finished inside it; generator lateness p99 "
        f"{(percentile(late, 99) if late.size else 0.0) * 1e3:.3f} ms, max "
        f"{(late.max() if late.size else 0.0) * 1e3:.3f} ms; XLA compiles "
        f"inside the window: {c_window}; set-up: {c_open} compiles in "
        f"{compiles.seconds:.1f} s, {compiles.hits} persistent-cache hits")

    # ---- correctness: served tokens against the reference
    cc = c["correct"]
    chosen = correct.sample(records, seed, int(cc["sample_requests"]),
                            int(cc["tokens_per_request"]))
    seeded = weights.Seeded(seed, weights.leaf_rules(cell.arch))
    gaps = (correct.served_gaps(cell.reference(), c, seeded, chosen,
                                control=control)
            if chosen else {"gaps": np.zeros(0), "control_gaps": np.zeros(0)})
    # closing the driver sheds what still waits ("driver closed"); any
    # other error, rejection or timeout is the program's
    errored = [r for r in records if r.finish in ("error", "rejected",
                                                  "timeout")
               and r.error != "driver closed"]
    # open loop: a request due in the window that did not finish; backlog
    # (cut by the window's close): one the program errored
    failed = ([r for r in in_window
               if r.finish not in ("length", "stop") or not r.tokens]
              if kind == "open_loop" else errored)
    served = sum(1 for r in records if r.tokens)
    checks, ok = judge(cc, gaps["gaps"], len(chosen), served, len(errored))

    run = Run(cell=cell, max_slots=ecfg.max_slots, records=records,
              in_window=in_window, t_open=t_open, t_close=t_close,
              counters=counters, spans=spans)
    if traced:
        run.peaks = device.peaks(dev["kind"])
        run.reduction = trace.reduce(trace.newest_xplane(trace_dir),
                                     marks["open"], marks["close"],
                                     trace.kernel_set([cell.arch]))
        run.work = traced_work(work.Model(c, cell.arch), run, marks["open"],
                               marks["close"], run.peaks)
        dev["busy_s"] = run.reduction.busy_s
        dev["window_s"] = run.reduction.window_s
        metrics = {}
        for m in cell.per_layer:
            v = cell.reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        metrics = e2e_metrics(run, t_waited)
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        metrics["peak_hbm_gib"] = {"value": dev["memory_peak_bytes"] / 2 ** 30,
                                   "unit": "GiB"}
        metrics = {m["name"]: metrics[m["name"]] for m in cell.end_to_end}

    line = {"correct": ok, "attempted": len(in_window) or len(records),
            "failed": len(failed), "metrics": metrics, "device": dev}
    if traced:
        top = sorted(run.reduction.ops.items(), key=lambda kv: -kv[1])[:10]
        line["breakdown"] = {
            "device_ops": [[k, v] for k, v in top],
            "idle_gaps": trace.name_gaps(
                run.reduction, [(e.name, e.ts, e.ts + e.dur) for e in spans
                                if e.track == ("engine", 0)])}
        for k, t in run.work.items():
            log(f"work {k}: {t.flops:.6g} flop, {t.bytes:.6g} B, roofline "
                f"{t.roofline_s:.6g} s ({t.compute_bound_s:.6g} s of it "
                f"bound by FLOP/s), kernel time "
                f"{run.reduction.kernel_s.get(k, 0.0):.6g} s")
    line["checks"] = checks
    if control:
        _, control_ok = judge(cc, gaps["control_gaps"], len(chosen), served,
                              len(errored))
        line["control"] = {"program": correct.stats(gaps["gaps"]),
                           "control": correct.stats(gaps["control_gaps"]),
                           "correct": control_ok}
    for name, chk in checks.items():
        log(f"check {name}: {chk['value']!r} (limit {chk['limit']!r})")
    return line


def judge(cc: Dict[str, Any], gaps: np.ndarray, compared: int, served: int,
          errored: int):
    """The checks of one reading of the gaps, each with its limit, and
    whether all hold: every compared gap statistic at or under its limit,
    at least ``tokens_to_compare`` tokens from as many requests as the
    sample asks for (or as were served), and no request errored."""
    stats = correct.stats(gaps)
    names = [k for k in correct.STATS if k in cc]
    checks = {k: {"value": stats[k], "limit": float(cc[k])} for k in names}
    checks["tokens_compared"] = {"value": int(gaps.size),
                                 "limit": int(cc["tokens_to_compare"])}
    checks["requests_compared"] = {
        "value": compared, "limit": min(served, 1 + int(cc["sample_requests"]))}
    checks["requests_errored"] = {"value": errored, "limit": 0}
    ok = (all(checks[k]["value"] <= checks[k]["limit"] for k in names)
          and gaps.size >= checks["tokens_compared"]["limit"]
          and compared >= checks["requests_compared"]["limit"]
          and compared > 0 and errored == 0)
    return checks, bool(ok)


def e2e_metrics(run: Run, t_waited: float) -> Dict[str, Dict[str, Any]]:
    seconds = run.t_close - run.t_open
    delivered = sum(1 for r in run.records for t in r.times
                    if run.t_open <= t < run.t_close)
    out = {"output_tokens_per_s": {"value": delivered / seconds,
                                   "unit": "tokens/s"}}
    if run.in_window:
        ttft, tpot = [], []
        for r in run.in_window:
            # a request that failed or never finished counts as missing:
            # its latency is at least the time it was waited for
            ok = r.finish in ("length", "stop") and len(r.times) > 1
            ttft.append((r.times[0] if ok else t_waited) - r.due)
            tpot.append((r.times[-1] - r.times[0]) / (len(r.times) - 1)
                        if ok else t_waited - r.due)
        out["ttft_p90_ms"] = {"value": percentile(ttft, 90) * 1e3,
                              "unit": "ms"}
        out["tpot_p90_ms"] = {"value": percentile(tpot, 90) * 1e3,
                              "unit": "ms"}
    return out
