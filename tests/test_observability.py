"""Zero-perturbation serving observability (serving contract v1.3).

The keystone assertions:

* **Zero perturbation** — a request's tokens are bit-identical with
  tracing on, off, or the bundle left unconfigured, on both schedulers.
* **Exact reconciliation** — under a VirtualClock, trace span timestamps
  and durations equal the ``RequestResult`` timing fields, and histogram
  percentiles equal numpy percentiles of those same numbers.
* **Monotonicity** — every registry counter is non-decreasing across
  snapshots of any seeded fault-plan run, and the page pool never
  over-counts (``pages_free + pages_used <= max_pages``).
* **Single clock** — a static guard bans raw wall-clock calls from the
  serving and model layers (everything routes through
  ``repro.runtime.clock``, which a ``VirtualClock`` substitutes).
* **A tiled driver thread** — under the ``EngineDriver`` the driver loop's
  spans and the step's leave no part of the thread uncovered, tracing on
  opens profiler annotations and tracing off none, the frontend's wait is
  a histogram and a span that agree, and compiles are counted.
"""

import json
import re
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import configs
from repro.models import init_params
from repro.runtime.monitor import (HEARTBEAT_SCHEMA, HeartbeatMonitor,
                                   StragglerDetector)
from repro.serving import (EngineConfig, FaultInjector, FaultPlan,
                           SamplingParams, SerialAdmitEngine, ServingEngine,
                           VirtualClock)
from repro.serving.frontend import EngineDriver
from repro.serving.observability import (DRIVER_PHASES, LATENCY_BUCKETS,
                                         PHASES, SERVING_METRICS,
                                         SPEC_BY_NAME, TRACK_ENGINE,
                                         Histogram, MetricsRegistry,
                                         Observability, TraceRecorder,
                                         compile_monitor, request_track)

ENGINES = [ServingEngine, SerialAdmitEngine]


@pytest.fixture(scope="module")
def small_model():
    cfg = configs.get_smoke_config("qwen2-1.5b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def traced_engine(small_model, ecfg=None, cls=ServingEngine, trace=True,
                  plan=None):
    """Engine on a VirtualClock with a trace-enabled bundle. The clock
    starts past zero so every timestamp is distinguishable from the
    unset-field sentinel 0.0."""
    cfg, params = small_model
    clock = VirtualClock(start=1000.0)
    inj = FaultInjector(plan or FaultPlan(), clock=clock)
    eng = cls(params, cfg, ecfg or EngineConfig(max_slots=2, capacity=32),
              injector=inj, observability=Observability(trace=trace))
    return eng, clock


def drive(eng, clock, dt=0.125):
    """Drain the engine, ticking the virtual clock between steps so spans
    and waits get distinct, deterministic durations."""
    while eng.queue or any(s is not None for s in eng.slots):
        clock.advance(dt)
        eng.step()


# ---------------------------------------------------------------------------
# registry + instruments
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_frozen_schema_is_well_formed(self):
        names = [s.name for s in SERVING_METRICS]
        assert len(names) == len(set(names))
        for s in SERVING_METRICS:
            assert s.kind in ("counter", "gauge", "histogram")
            assert s.name.startswith("serving_")
            if s.kind == "counter":
                assert s.name.endswith("_total"), s.name
            if s.kind == "histogram":
                assert s.buckets, s.name
        # every engine phase has its frozen seconds counter
        for p in PHASES:
            assert f"serving_phase_{p}_seconds_total" in SPEC_BY_NAME

    def test_frozen_kind_is_enforced(self):
        reg = MetricsRegistry()
        with pytest.raises(AssertionError):
            reg.gauge("serving_requests_completed_total")  # frozen: counter

    def test_duplicate_registration_rejected(self):
        reg = MetricsRegistry()
        reg.counter("x_total")
        with pytest.raises(ValueError):
            reg.counter("x_total")

    def test_polled_counter_reads_live_value(self):
        reg = MetricsRegistry()
        box = {"n": 0}
        assert reg.counter("polled_total", poll=lambda: box["n"]) is None
        box["n"] = 7
        assert reg.value("polled_total") == 7
        assert reg.counters() == {"polled_total": 7}

    def test_histogram_exact_percentiles_and_buckets(self):
        h = Histogram(buckets=(1.0, 2.0, 4.0))
        for v in (0.5, 1.5, 3.0, 8.0):
            h.observe(v)
        assert h.count == 4 and h.max == 8.0
        assert h.bucket_counts == [1, 1, 1, 1]  # per-bucket, +Inf last
        assert h.percentile(50) == float(np.percentile([0.5, 1.5, 3.0, 8.0],
                                                       50))
        assert h.percentile(100) == 8.0
        assert Histogram().percentile(99) == 0.0  # empty → 0.0, not NaN

    def test_histogram_window_bounds_memory(self):
        h = Histogram(buckets=(1.0,), window=8)
        for i in range(100):
            h.observe(float(i))
        assert h.count == 100              # cumulative stats keep counting
        assert len(h._samples) == 8        # raw window stays bounded
        assert h.percentile(0) == 92.0     # ...over the most recent 8

    def test_prometheus_exposition(self):
        reg = MetricsRegistry()
        c = reg.counter("serving_requests_completed_total",
                        help="requests finished")
        c.inc(3)
        hist = reg.histogram("serving_ttft_seconds",
                             buckets=LATENCY_BUCKETS, help="ttft")
        hist.observe(0.3)
        text = reg.render_prometheus()
        assert "# TYPE serving_requests_completed_total counter" in text
        assert "serving_requests_completed_total 3" in text
        assert "# TYPE serving_ttft_seconds histogram" in text
        assert 'serving_ttft_seconds_bucket{le="+Inf"} 1' in text
        assert "serving_ttft_seconds_count 1" in text
        # cumulative: every bucket >= 0.5 already includes the 0.3 sample
        assert 'serving_ttft_seconds_bucket{le="0.5"} 1' in text

    def test_jsonl_line_round_trips(self):
        reg = MetricsRegistry()
        reg.counter("a_total").inc(2)
        reg.histogram("h_seconds").observe(1.0)
        snap = json.loads(reg.jsonl_line(t=5.0))
        assert snap["t"] == 5.0 and snap["a_total"] == 2
        assert snap["h_seconds"]["count"] == 1


# ---------------------------------------------------------------------------
# trace recorder
# ---------------------------------------------------------------------------

class TestTraceRecorder:
    def test_ring_drops_oldest_and_counts(self):
        tr = TraceRecorder(capacity=4)
        for i in range(10):
            tr.instant(f"e{i}", ("engine", 0), float(i))
        assert len(tr) == 4 and tr.dropped == 6
        assert [e.name for e in tr.events()] == ["e6", "e7", "e8", "e9"]
        assert tr.chrome_trace()["otherData"]["dropped_events"] == 6

    def test_chrome_trace_format(self, tmp_path):
        tr = TraceRecorder()
        tr.complete("step", ("engine", 0), 1.0, 1.5,
                    args={"engine_step": 1})
        tr.instant("first_token", request_track(3), 1.25)
        doc = tr.chrome_trace()
        evs = doc["traceEvents"]
        span = next(e for e in evs if e.get("ph") == "X")
        assert span["ts"] == 1.0e6 and span["dur"] == 0.5e6  # microseconds
        inst = next(e for e in evs if e.get("ph") == "i")
        assert inst["s"] == "t" and inst["tid"] == 3
        # metadata names both tracks
        pnames = {e["args"]["name"] for e in evs
                  if e.get("name") == "process_name"}
        assert pnames == {"engine", "requests"}
        p = tmp_path / "trace.json"
        tr.write(p)
        assert json.loads(p.read_text())["displayTimeUnit"] == "ms"


# ---------------------------------------------------------------------------
# engine integration: reconciliation + zero perturbation
# ---------------------------------------------------------------------------

class TestEngineTracing:
    @pytest.mark.parametrize("cls", ENGINES)
    def test_spans_reconcile_with_result_timestamps(self, small_model, cls):
        """Under the VirtualClock, the trace is fully deterministic and the
        per-request spans equal the RequestResult timing fields exactly."""
        eng, clock = traced_engine(small_model, cls=cls)
        hs = [eng.submit([1, 2, 3], SamplingParams(max_new_tokens=4))]
        clock.advance(0.5)
        hs.append(eng.submit([4, 5], SamplingParams(max_new_tokens=3)))
        drive(eng, clock)
        results = [h.result() for h in hs]
        evs = eng.obs.trace.events()
        for h, r in zip(hs, results):
            track = request_track(h.uid)
            by_name = {e.name: e for e in evs if e.track == track}
            req = by_name["request"]
            assert req.ts == r.t_submit
            assert req.ts + req.dur == r.t_done
            assert req.args["finish_reason"] == r.finish_reason
            assert req.args["tokens"] == len(r.tokens)
            assert by_name["queued"].dur == pytest.approx(r.queue_wait)
            assert by_name["first_token"].ts == r.t_first
            decode = by_name["decode"]
            assert decode.ts == r.t_first and decode.ts + decode.dur == r.t_done
            assert by_name["prefill"].ts == h.t_admit
            # lifecycle ordering on the virtual timeline
            assert (by_name["submitted"].ts <= by_name["admitted"].ts
                    <= by_name["first_token"].ts <= by_name["retired"].ts)

    @pytest.mark.parametrize("cls", ENGINES)
    def test_histograms_reconcile_with_results(self, small_model, cls):
        eng, clock = traced_engine(small_model, cls=cls)
        hs = []
        for prompt, n in (([1, 2, 3], 4), ([4, 5], 3), ([6], 2)):
            hs.append(eng.submit(prompt, SamplingParams(max_new_tokens=n)))
            clock.advance(0.25)
        drive(eng, clock)
        results = [h.result() for h in hs]
        reg = eng.obs.registry
        ttfts = np.asarray([r.ttft for r in results])
        waits = np.asarray([r.queue_wait for r in results])
        for q in (50, 90, 99):
            assert reg.get_histogram("serving_ttft_seconds").percentile(q) \
                == float(np.percentile(ttfts, q))
            assert reg.get_histogram(
                "serving_queue_wait_seconds").percentile(q) \
                == float(np.percentile(waits, q))
        assert reg.value("serving_tokens_generated_total") \
            == sum(len(r.tokens) for r in results)

    @pytest.mark.parametrize("cls", ENGINES)
    def test_zero_perturbation(self, small_model, cls):
        """Bit-identical tokens with tracing on, off, and unconfigured —
        cooperatively and through the driver's thread — and no extra jit
        compilations from instrumentation."""
        cfg, params = small_model
        sp = SamplingParams(max_new_tokens=6, temperature=0.8, seed=11)
        prompts = [([5, 9, 17, 2], sp),
                   ([1, 2], SamplingParams(max_new_tokens=4))]
        runs = []
        for via_driver in (False, True):
            for obs in (None, Observability(trace=False),
                        Observability(trace=True)):
                eng = cls(params, cfg, EngineConfig(max_slots=2, capacity=32),
                          observability=obs)
                if via_driver:
                    drv = EngineDriver(eng).start()
                    hs = [drv.submit(p, q) for p, q in prompts]
                    tokens = [h.result(timeout=120).tokens for h in hs]
                    drv.close()
                else:
                    hs = [eng.submit(p, q) for p, q in prompts]
                    eng.run()
                    tokens = [h.result().tokens for h in hs]
                runs.append((tokens,
                             eng.compile_stats()["n_prefill_compiles"],
                             eng.compile_stats()["n_decode_compiles"]))
        assert all(r == runs[0] for r in runs), runs

    def test_step_phase_spans_and_counters(self, small_model):
        eng, _ = traced_engine(small_model)
        eng.submit([1, 2, 3], SamplingParams(max_new_tokens=3))
        eng.run()
        reg = eng.obs.registry
        # phase seconds flowed into their frozen counters (virtual clock
        # never advances on its own, so values are >= 0 and finite)
        for p in ("sweep", "admit", "prefill_dispatch", "decode_dispatch",
                  "collect"):
            assert reg.value(f"serving_phase_{p}_seconds_total") >= 0.0
        steps = [e for e in eng.obs.trace.events()
                 if e.name == "step" and e.track == ("engine", 0)]
        assert len(steps) == eng.engine_steps
        assert [e.args["engine_step"] for e in steps] \
            == list(range(1, eng.engine_steps + 1))

    def test_trace_ring_overflow_reaches_registry(self, small_model):
        eng, _ = traced_engine(small_model)
        eng.obs.trace.capacity = 4  # shrink post-hoc: force overflow
        eng.submit([1, 2, 3], SamplingParams(max_new_tokens=4))
        eng.run()
        assert eng.obs.trace.dropped > 0
        assert eng.obs.registry.value("serving_trace_dropped_total") \
            == eng.obs.trace.dropped

    def test_health_reads_the_registry(self, small_model):
        """health() is derived from the registry — the two surfaces can
        never disagree."""
        eng, _ = traced_engine(small_model)
        eng.submit([1, 2], SamplingParams(max_new_tokens=2))
        eng.run()
        snap, reg = eng.health(), eng.obs.registry
        assert snap.completed == reg.value("serving_requests_completed_total")
        assert snap.queue_depth == reg.value("serving_queue_depth")
        assert snap.free_slots == reg.value("serving_free_slots")
        d = eng.obs.digest()
        assert d["serving_requests_completed_total"] == snap.completed
        assert "ttft_p50_s" in d


# ---------------------------------------------------------------------------
# the driver thread: tiling spans, profiler annotations, the frontend's wait,
# compiles
# ---------------------------------------------------------------------------

def _ticking(fn, clock, dt):
    """``fn`` with the virtual clock advanced ``dt`` on entry and on exit:
    time passes only inside the work the driver does."""
    def wrapped(*a, **k):
        clock.advance(dt)
        try:
            return fn(*a, **k)
        finally:
            clock.advance(dt)
    return wrapped


def _uncovered(lo, hi, pieces):
    """Length of [lo, hi] that no (a, b) of ``pieces`` covers."""
    t, hole = lo, 0.0
    for a, b in sorted(pieces):
        if b <= t:
            continue
        hole += max(0.0, min(a, hi) - t)
        t = max(t, b)
        if t >= hi:
            break
    return hole + max(0.0, hi - t)


class TestDriverTracing:
    def test_driver_spans_tile_the_thread(self, small_model):
        """With time passing only inside the work of each phase (the
        driver's lock, calls, offers, fan-out and parking; the step's
        admission, sampling and collection), a chat-like load through the
        driver leaves no hole over 1 us of virtual time between
        ``driver_loop`` spans or inside one outside its children."""
        eng, clock = traced_engine(small_model, EngineConfig(
            max_slots=2, capacity=32, prefill_chunk=4))
        drv = EngineDriver(eng)
        dt = 1e-3
        for name in ("_service_calls_locked", "_apply_cancels_locked",
                     "_sweep_frontend_locked", "_offer_locked", "_pump"):
            setattr(drv, name, _ticking(getattr(drv, name), clock, dt))
        drv._cond.acquire = _ticking(drv._cond.acquire, clock, dt)
        drv._cond.wait = _ticking(drv._cond.wait, clock, dt)
        for name in ("_admit", "_sample_first", "_collect", "_fleet_arrays"):
            setattr(eng, name, _ticking(getattr(eng, name), clock, dt))
        drv.start()
        hs = [drv.submit(list(range(1, 2 + 3 * i)),
                         SamplingParams(max_new_tokens=3 + i, seed=i))
              for i in range(5)]
        for h in hs:
            assert h.result(timeout=120).finish_reason == "length"
        drv.close()
        evs = [e for e in eng.obs.trace.events()
               if e.track == TRACK_ENGINE and e.ph == "X"]
        loops = sorted((e.ts, e.ts + e.dur) for e in evs
                       if e.name == "driver_loop")
        children = [(e.ts, e.ts + e.dur) for e in evs
                    if e.name in DRIVER_PHASES[1:] + ("step",)]
        assert len(loops) > len(hs)
        assert {e.name for e in evs} >= set(DRIVER_PHASES) | {
            "step", "decode_prepare", "prefill_prepare"}
        for (a0, a1), (b0, _) in zip(loops, loops[1:]):
            assert b0 - a1 <= 1e-6
        for lo, hi in loops:
            inside = [(a, b) for a, b in children if lo <= a and b <= hi]
            assert _uncovered(lo, hi, inside) <= 1e-6, (lo, hi)
        # every phase fed its frozen seconds counter
        reg = eng.obs.registry
        for p in DRIVER_PHASES:
            assert reg.value(f"serving_phase_{p}_seconds_total") > 0.0

    @pytest.mark.parametrize("trace", [False, True])
    def test_annotations_only_while_tracing(self, small_model, monkeypatch,
                                            trace):
        """Tracing off: no recorder, and not one profiler annotation is
        made; tracing on: every span and step opens one."""
        made = {"span": 0, "step": 0}

        def counting(kind, cls):
            class Counted(cls):
                def __init__(self, *a, **k):
                    made[kind] += 1
                    super().__init__(*a, **k)
            return Counted

        monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                            counting("span", jax.profiler.TraceAnnotation))
        monkeypatch.setattr(jax.profiler, "StepTraceAnnotation",
                            counting("step",
                                     jax.profiler.StepTraceAnnotation))
        cfg, params = small_model
        eng = ServingEngine(params, cfg, EngineConfig(max_slots=2,
                                                      capacity=32),
                            observability=Observability(trace=trace))
        drv = EngineDriver(eng).start()
        h = drv.submit([1, 2, 3], SamplingParams(max_new_tokens=4))
        assert h.result(timeout=120).finish_reason == "length"
        drv.close()
        if not trace:
            assert eng.obs.trace is None
            assert made == {"span": 0, "step": 0}
        else:
            evs = [e for e in eng.obs.trace.events()
                   if e.track == TRACK_ENGINE
                   and e.name not in ("step", "compile")]
            assert made["step"] == eng.engine_steps > 0
            assert made["span"] == len(evs) > 0

    def test_frontend_wait_histogram_matches_spans(self, small_model):
        """One slot and a queue of requests: the fair-queue wait histogram
        holds exactly t_offer - t_submit of each, and so do the
        ``frontend_queued`` spans."""
        cfg, params = small_model
        eng = ServingEngine(params, cfg, EngineConfig(max_slots=1,
                                                      capacity=32),
                            observability=Observability(trace=True))
        drv = EngineDriver(eng).start()
        hs = [drv.submit([1 + i, 2, 3], SamplingParams(max_new_tokens=3))
              for i in range(5)]
        for h in hs:
            h.result(timeout=120)
        drv.close()
        waits = np.asarray([h.t_offer - h.t_submit for h in hs])
        assert (waits >= 0).all() and waits.max() > 0
        spans = {e.track[1]: e for e in eng.obs.trace.events()
                 if e.name == "frontend_queued"}
        durs = np.asarray([spans[h.uid].dur for h in hs])
        hist = eng.obs.registry.get_histogram(
            "serving_frontend_queue_wait_seconds")
        assert hist.count == len(hs)
        for q in (50, 90, 99):
            assert hist.percentile(q) == float(np.percentile(waits, q)) \
                == float(np.percentile(durs, q))
        for h in hs:
            assert spans[h.uid].ts == h.t_submit
            assert h.t_submit <= h.t_offer <= h.t_admit

    def test_frontend_shed_ends_its_span_with_the_reason(self, small_model):
        cfg, params = small_model
        eng = ServingEngine(params, cfg, EngineConfig(max_slots=1,
                                                      capacity=32),
                            observability=Observability(trace=True))
        drv = EngineDriver(eng)
        drv.drain(timeout=0)            # not started: intake is closed
        h = drv.submit([1, 2], SamplingParams(max_new_tokens=2))
        assert h.finish_reason == "rejected"
        [span] = [e for e in eng.obs.trace.events()
                  if e.name == "frontend_queued"]
        assert span.track == request_track(h.uid)
        assert span.ts == h.t_submit and span.ts + span.dur == h.t_done
        assert span.args["finish_reason"] == "rejected"

    def test_compile_counter_counts_backend_compiles(self, small_model):
        cfg, params = small_model
        eng = ServingEngine(params, cfg, EngineConfig(max_slots=1,
                                                      capacity=32))
        reg = eng.obs.registry
        f = jax.jit(lambda x: x * 3.0 + 1.0)
        x = np.ones((7, 13), np.float32)   # a shape no other test jits
        n0 = reg.value("serving_compiles_total")
        s0 = reg.value("serving_compile_seconds_total")
        f(x)
        assert reg.value("serving_compiles_total") == n0 + 1
        assert reg.value("serving_compile_seconds_total") > s0
        f(x)
        assert reg.value("serving_compiles_total") == n0 + 1
        assert reg.value("serving_compiles_total") == compile_monitor().count

    def test_compile_on_the_driver_thread_is_a_span(self, small_model):
        """The driver's first step compiles its programs: each compile is
        a ``compile`` span on the engine track, inside a step."""
        cfg, params = small_model
        eng = ServingEngine(params, cfg, EngineConfig(max_slots=1,
                                                      capacity=24),
                            observability=Observability(trace=True))
        drv = EngineDriver(eng).start()
        drv.submit([3, 1, 4], SamplingParams(max_new_tokens=2)).result(
            timeout=120)
        drv.close()
        evs = eng.obs.trace.events()
        compiles = [e for e in evs if e.name == "compile"]
        steps = [e for e in evs if e.name == "step"]
        assert compiles and all(e.track == TRACK_ENGINE for e in compiles)
        assert all(any(s.ts <= c.ts + c.dur <= s.ts + s.dur for s in steps)
                   for c in compiles)


# ---------------------------------------------------------------------------
# property test: monotone counters + page-pool conservation under faults
# ---------------------------------------------------------------------------

class TestCounterMonotonicity:
    def _drive_and_check(self, eng, clock, submits):
        prev = eng.obs.registry.counters()
        paged = eng.paged
        max_pages = eng.alloc.n_pages if paged else None
        for i, (prompt, sp) in enumerate(submits):
            eng.submit(prompt, sp)
            clock.advance(0.25)
            eng.step()
            cur = eng.obs.registry.counters()
            for name, v in cur.items():
                assert v >= prev[name], f"{name} decreased: {prev[name]}->{v}"
            if paged:
                free = eng.obs.registry.value("serving_pages_free")
                used = eng.obs.registry.value("serving_pages_used")
                assert free + used <= max_pages
            prev = cur
        while eng.queue or any(s is not None for s in eng.slots):
            clock.advance(0.25)
            eng.step()
            cur = eng.obs.registry.counters()
            for name, v in cur.items():
                assert v >= prev[name], f"{name} decreased: {prev[name]}->{v}"
            if paged:
                free = eng.obs.registry.value("serving_pages_free")
                used = eng.obs.registry.value("serving_pages_used")
                assert free + used <= max_pages
            prev = cur

    def test_counters_monotone_under_fault_plan(self, small_model):
        """Across a run with NaN poisoning, deadline expiry, and shedding,
        every counter in successive snapshots is non-decreasing."""
        plan = (FaultPlan().nan_logits(uid=0, gen_index=2)
                .stall_clock(at_step=5, advance_s=60.0))
        eng, clock = traced_engine(
            small_model, EngineConfig(max_slots=2, capacity=32, max_queue=3),
            plan=plan)
        submits = [([1 + i, 2, 3], SamplingParams(
            max_new_tokens=4 + i, deadline_s=30.0, seed=i))
            for i in range(6)]
        self._drive_and_check(eng, clock, submits)
        # the plan really did exercise the fault paths
        reg = eng.obs.registry
        assert reg.value("serving_requests_error_total") >= 1
        assert reg.value("serving_requests_timeout_total") \
            + reg.value("serving_requests_completed_total") >= 1

    def test_counters_monotone_paged_pool_conserved(self, small_model):
        # prefix_cache off so a drained pool owes zero pages (the cache
        # intentionally keeps published prefix pages referenced)
        eng, clock = traced_engine(small_model, EngineConfig(
            max_slots=2, capacity=32, kv_layout="paged", page_size=8,
            prefix_cache=False))
        submits = [([1, 2, 3, 4, 5, 6, 7, 8, 9], SamplingParams(
            max_new_tokens=6, seed=i)) for i in range(4)]
        self._drive_and_check(eng, clock, submits)
        reg = eng.obs.registry
        assert reg.value("serving_pages_alloc_total") > 0
        assert reg.value("serving_pages_release_total") > 0
        # drained: every page back in the pool
        assert reg.value("serving_pages_used") == 0


# ---------------------------------------------------------------------------
# the single-clock invariant (static guard)
# ---------------------------------------------------------------------------

class TestClockGuard:
    def test_no_raw_wall_clock_in_serving_or_models(self):
        """Every timestamp in the serving and model layers must route
        through repro.runtime.clock, so a VirtualClock substitution covers
        *all* of them. A raw time.time()/perf_counter() call would fork the
        time domain and silently break trace determinism."""
        src = Path(__file__).resolve().parent.parent / "src" / "repro"
        pat = re.compile(r"\btime\.(time|perf_counter|monotonic)\s*\(")
        offenders = []
        for layer in ("serving", "models"):
            for p in sorted((src / layer).rglob("*.py")):
                for i, line in enumerate(p.read_text().splitlines(), 1):
                    if pat.search(line):
                        offenders.append(f"{p.relative_to(src)}:{i}")
        assert not offenders, (
            "raw wall-clock calls found (route through repro.runtime.clock "
            f"instead): {offenders}")

    def test_clock_module_is_the_one_wall_clock_owner(self):
        from repro.runtime import clock as rtclock
        assert rtclock.now() <= rtclock.now()          # monotone
        assert isinstance(rtclock.wall_now(), float)


# ---------------------------------------------------------------------------
# heartbeat schema versioning (satellite)
# ---------------------------------------------------------------------------

class TestHeartbeatSchema:
    def test_current_beat_carries_schema_and_digest(self, small_model,
                                                    tmp_path):
        eng, _ = traced_engine(small_model)
        eng.submit([1, 2], SamplingParams(max_new_tokens=2))
        eng.run()
        mon = HeartbeatMonitor(str(tmp_path), host_id=0)
        eng.health().beat(mon, step_time_s=0.1, metrics=eng.obs.digest())
        [beat] = StragglerDetector(str(tmp_path)).read()
        assert beat["schema"] == HEARTBEAT_SCHEMA
        assert beat["serving_requests_completed_total"] == 1
        assert beat["queue_depth"] == 0

    def test_pre_metrics_heartbeat_still_parses(self, tmp_path):
        """A v1 payload (pre-paging/pre-metrics writers: no schema, no
        step_time_s, no digest keys) must parse and assess — a fleet
        mid-upgrade never KeyErrors the detector."""
        d = tmp_path / "heartbeats"
        d.mkdir()
        (d / "host0000.json").write_text(json.dumps(
            {"host": 0, "step": 12, "t": 1000.0}))
        (d / "host0001.json").write_text(json.dumps(   # v2 writer alongside
            {"schema": 2, "host": 1, "step": 12, "t": 1000.0,
             "step_time_s": 0.5, "serving_requests_completed_total": 3}))
        (d / "host0002.json").write_text("{not json")  # torn read
        det = StragglerDetector(str(tmp_path), dead_after_s=120.0)
        beats = det.read()
        assert [b["host"] for b in beats] == [0, 1]
        assert beats[0]["schema"] == 1 and beats[0]["step_time_s"] is None
        report = det.assess(now=1001.0)
        assert sorted(report["healthy"]) == [0, 1]
        # the straggler median ignores hosts that report no step time
        assert report["median_step_s"] == 0.5

    def test_unassessable_payload_skipped_not_crashed(self, tmp_path):
        d = tmp_path / "heartbeats"
        d.mkdir()
        (d / "host0000.json").write_text(json.dumps({"step": 3}))  # no host/t
        (d / "host0001.json").write_text(json.dumps([1, 2, 3]))    # not a dict
        assert StragglerDetector(str(tmp_path)).read() == []
