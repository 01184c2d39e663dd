"""The device's idle time split by what the engine's thread was doing
(``harness/idle.py``), the per-layer metrics that read the driver's and
the frontend's spans, and the clock they share with a profiler capture."""

from __future__ import annotations

import time
from types import SimpleNamespace

import pytest

import bench_tiny_tree as tiny  # noqa: F401  (sys.path)

from harness import idle, spec, trace
from repro.serving.observability import TraceEvent

ENGINE = ("engine", 0)


def _ev(name, t0, t1, track=ENGINE):
    return TraceEvent(name, "phase", "X", track, t0, t1 - t0)


def _reader(name):
    return spec.load_module(tiny.BENCH / "metrics" / f"{name}.py").read


def test_split_follows_the_innermost_span_across_a_gap():
    # seconds on the engine's clock; the trace clock runs 5 s ahead
    spans = [("driver_loop", 1.0, 2.0), ("driver_lock", 1.0, 1.1),
             ("step", 1.2, 1.9), ("decode_sync", 1.5, 1.8),
             ("compile", 1.6, 1.7), ("driver_pump", 1.9, 2.0),
             ("driver_loop", 2.5, 3.0), ("driver_idle", 2.5, 3.0),
             ("recovery", 3.2, 3.3)]
    off = 5e9
    gaps = [(6.05e9, 6.25e9),     # lock 0.05, loop 0.1, step 0.05
            (6.45e9, 6.65e9),     # step 0.05, sync 0.1, compile 0.05
            (6.85e9, 6.95e9),     # step 0.05, pump 0.05
            (6.98e9, 7.6e9),      # pump 0.02, none 0.5, idle 0.1
            (8.1e9, 8.4e9)]       # none 0.1, recovery 0.1, none 0.1
    got = idle.split(gaps, off, spans)
    assert got == pytest.approx({"driver": 0.05 + 0.1 + 0.05 + 0.02,
                                 "engine": 0.05 + 0.05 + 0.1 + 0.05
                                 + 0.05,
                                 "driver_idle": 0.1,
                                 "unattributed": 0.5 + 0.3})
    assert sum(got.values()) == pytest.approx(
        sum(b - a for a, b in gaps) / 1e9)
    assert idle.split(gaps, None, spans) is None


def test_layer_names():
    assert idle.layer_of("driver_offer") == "driver"
    assert idle.layer_of("driver_loop") == "driver"
    assert idle.layer_of("driver_idle") == "driver_idle"
    for name in ("step", "compile", "sweep", "decode_prepare",
                 "prefill_prepare", "page_maint"):
        assert idle.layer_of(name) == "engine"
    assert idle.layer_of(None) == "unattributed"
    assert idle.layer_of("recovery") == "unattributed"


def _run(spans, gaps=((1.2e9, 1.4e9),), offset_ns=0.0, window_s=2.0,
         in_window=()):
    red = trace.Reduction(window_s=window_s, busy_s=window_s - 0.2, ops={},
                          kernel_s={}, gaps=list(gaps), offset_ns=offset_ns,
                          n_chips=1)
    return SimpleNamespace(reduction=red, spans=spans,
                           in_window=list(in_window))


def test_host_idle_shares_read_the_split():
    spans = [_ev("driver_loop", 1.0, 1.5), _ev("step", 1.1, 1.3),
             _ev("decode_dispatch", 1.1, 1.25), _ev("driver_pump", 1.3, 1.5),
             _ev("driver_loop", 5.0, 5.5, track=("requests", 3))]
    run = _run(spans)
    # the gap 1.2-1.4 s: dispatch 0.05, step 0.05, pump 0.1; window 2 s
    for mix in ("chat", "batch"):
        assert _reader(f"driver.host_idle_share.{mix}")(run) \
            == pytest.approx(0.1 / 2.0)
        assert _reader(f"engine.host_idle_share.{mix}")(run) \
            == pytest.approx(0.1 / 2.0)
    s = idle.shares(run)
    assert sum(s.values()) == pytest.approx(0.2 / 2.0)


@pytest.mark.parametrize("case", ["no_driver_spans", "no_offset",
                                  "no_trace"])
def test_host_idle_shares_read_nothing_without_what_they_need(case):
    """A program that records no driver-loop spans (or a run whose clocks
    could not be aligned, or that was not traced) gives no reading."""
    spans = [_ev("step", 1.1, 1.3), _ev("decode_dispatch", 1.1, 1.25)]
    if case != "no_driver_spans":
        spans.append(_ev("driver_loop", 1.0, 1.5))
    run = _run(spans, offset_ns=None if case == "no_offset" else 0.0)
    if case == "no_trace":
        run.reduction = None
    for name in ("driver.host_idle_share.chat", "engine.host_idle_share.chat",
                 "driver.host_idle_share.batch",
                 "engine.host_idle_share.batch"):
        assert _reader(name)(run) is None


def test_fair_wait_and_admit_to_first_read_request_spans():
    recs = [SimpleNamespace(uid=u) for u in range(1, 11)]
    spans = []
    for r in recs:
        track = ("requests", r.uid)
        spans.append(_ev("frontend_queued", 0.0, 0.1 * r.uid, track))
        spans.append(_ev("queued", 1.0, 1.5, track))
        spans.append(_ev("prefill", 2.0, 2.0 + 0.2 * r.uid, track))
    # a request outside the window, and one with no frontend span
    spans.append(_ev("frontend_queued", 0.0, 99.0, ("requests", 42)))
    spans.append(_ev("prefill", 0.0, 99.0, ("requests", 43)))
    run = _run(spans, in_window=recs + [SimpleNamespace(uid=43)])
    # nearest rank p90 of ten: the ninth
    assert _reader("driver.fair_wait_p90_ms")(run) == pytest.approx(900.0)
    assert _reader("engine.admit_to_first_p90_ms")(run) \
        == pytest.approx(1800.0)
    bare = _run([e for e in spans if e.name != "frontend_queued"],
                in_window=recs)
    assert _reader("driver.fair_wait_p90_ms")(bare) is None
    assert _reader("engine.admit_to_first_p90_ms")(bare) is None


def test_recorder_spans_land_on_their_profiler_annotations(tmp_path):
    """A profiler capture on the CPU of a few engine steps with tracing on:
    each phase span, moved by the offset ``trace.reduce`` takes from the
    two window marks, lies within 0.5 ms of its annotation on the host
    plane."""
    import jax
    from jax.profiler import ProfileData

    from repro import configs
    from repro.models import init_params
    from repro.serving import EngineConfig, SamplingParams, ServingEngine
    from repro.serving.observability import Observability

    cfg = configs.get_smoke_config("qwen2-1.5b")
    eng = ServingEngine(init_params(cfg, jax.random.PRNGKey(0)), cfg,
                        EngineConfig(max_slots=2, capacity=32),
                        observability=Observability(trace=True))
    eng.submit([1, 2, 3], SamplingParams(max_new_tokens=4))
    eng.run()                                   # compile outside the capture
    n_before = len(eng.obs.trace)
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.MARK_OPEN):
        pc_open = time.perf_counter()
    eng.submit([4, 5, 6, 7], SamplingParams(max_new_tokens=6))
    eng.run()
    with jax.profiler.TraceAnnotation(trace.MARK_CLOSE):
        pc_close = time.perf_counter()
    jax.profiler.stop_trace()
    xplane = trace.newest_xplane(tmp_path)
    red = trace.reduce(xplane, pc_open, pc_close)
    assert red.offset_ns is not None
    spans = [e for e in eng.obs.trace.events()[n_before:]
             if e.track == ENGINE and e.ph == "X"]
    names = {e.name for e in spans}
    assert {"step", "sweep", "admit", "prefill_prepare", "decode_prepare",
            "decode_dispatch"} <= names
    host = {}
    for plane in ProfileData.from_file(str(xplane)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in names:
                        host.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
    for e in spans:
        a = e.ts * 1e9 + red.offset_ns
        b = (e.ts + e.dur) * 1e9 + red.offset_ns
        best = min(host[e.name],
                   key=lambda ab: abs(ab[0] - a) + abs(ab[1] - b))
        assert abs(best[0] - a) <= 0.5e6 and abs(best[1] - b) <= 0.5e6, \
            (e.name, best[0] - a, best[1] - b)
