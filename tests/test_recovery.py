"""Supervised engine recovery (v1.5): crash-restart, deterministic
replay, suspect blacklisting, the hung-step watchdog, and the
crash-loop circuit breaker.

The keystone assertion, inherited from the determinism contract: a
request replayed onto a rebuilt engine regenerates from token 0 and the
handle's delivered-token cursor dedups the already-streamed prefix, so
the client-visible stream across any number of engine generations is
bit-identical to a crash-free run — no duplicate, no gap."""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

import jax
import pytest

from repro import configs
from repro.models import init_params
from repro.runtime.monitor import (HeartbeatMonitor, HealthSnapshot,
                                   StragglerDetector)
from repro.serving import (EngineConfig, FaultInjector, FaultPlan,
                           SamplingParams, ServingEngine, VirtualClock)
from repro.serving.frontend import (DegradedError, EngineDriver,
                                    EngineSupervisor, StepTimeout,
                                    ThreadedHttpServer)

ROOT = Path(__file__).resolve().parents[1]

pytestmark = pytest.mark.timeout(300)  # a wedged recovery must fail fast

ECFG = dict(max_slots=2, capacity=64, decode_chunk=2, prefill_chunk=16)


def _wait_until(pred, timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.005)
    return False


@pytest.fixture(scope="module")
def small_model():
    cfg = configs.get_smoke_config("qwen2-1.5b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _oracle(small_model, jobs):
    """Crash-free reference streams for [(prompt, SamplingParams), ...]."""
    cfg, params = small_model
    eng = ServingEngine(params, cfg, EngineConfig(**ECFG))
    hs = [eng.submit(p, sp) for p, sp in jobs]
    eng.run()
    return [tuple(h.output) for h in hs]


def _supervisor(small_model, plans, clocks=None, **kw):
    """Supervisor whose factory arms ``plans[g]`` on generation g (clean
    past the end of the list). ``clocks[g]`` likewise pins a VirtualClock
    per generation. Injectors are recorded on the returned supervisor as
    ``._injectors`` so tests can release stalls in teardown."""
    cfg, params = small_model
    built = {"n": 0}
    injectors = []

    def factory():
        g = built["n"]
        built["n"] += 1
        plan = plans[g] if g < len(plans) else FaultPlan()
        clock = clocks[g] if clocks is not None and g < len(clocks) else None
        inj = FaultInjector(plan, clock=clock)
        injectors.append(inj)
        return ServingEngine(params, cfg, EngineConfig(**ECFG), injector=inj)

    kw.setdefault("restart_backoff_s", 0.01)
    sup = EngineSupervisor(factory, **kw)
    sup._injectors = injectors
    return sup.start()


def _post(base, obj, path="/v1/completions", method="POST"):
    data = json.dumps(obj).encode() if obj is not None else None
    req = urllib.request.Request(base + path, data=data, method=method,
                                 headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=120) as resp:
            return resp.status, dict(resp.headers), json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), json.loads(e.read())


def _sse(base, obj):
    req = urllib.request.Request(base + "/v1/completions",
                                 data=json.dumps(obj).encode(),
                                 headers={"Content-Type": "application/json"})
    tokens, result = [], None
    with urllib.request.urlopen(req, timeout=120) as resp:
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data: ") or line == "data: [DONE]":
                continue
            ev = json.loads(line[len("data: "):])
            if "token" in ev:
                tokens.append(ev["token"])
            else:
                result = ev
    return tokens, result


# ---------------------------------------------------------------------------
# crash → rebuild → replay, bit-identical
# ---------------------------------------------------------------------------

class TestCrashReplay:
    def test_crash_mid_decode_replays_bit_identical(self, small_model):
        """Ambiguous mid-decode crash: both residents replay from token 0
        on the rebuilt engine; the spliced streams equal the crash-free
        oracle and every token index is delivered exactly once."""
        jobs = [([5, 9, 17, 2], SamplingParams(max_new_tokens=8, seed=0)),
                ([1, 2, 3], SamplingParams(max_new_tokens=8, seed=1))]
        ref = _oracle(small_model, jobs)
        sup = _supervisor(small_model,
                          [FaultPlan().engine_crash("decode", 2)],
                          blacklist_after=9)
        try:
            events = [[], []]
            handles = []
            for i, (p, sp) in enumerate(jobs):
                h = sup.submit(p, sp)
                h.subscribe(events[i].append)
                handles.append(h)
            results = [h.result(timeout=120) for h in handles]
            assert [r.finish_reason for r in results] == ["length", "length"]
            assert [tuple(r.tokens) for r in results] == ref
            # delivered exactly once, in order, across the generation swap
            for i, evs in enumerate(events):
                toks = [e for e in evs if e[0] == "token"]
                assert [e[1] for e in toks] == list(range(8))
                assert tuple(e[2] for e in toks) == ref[i]
            assert sup.generation == 1 and sup.restarts == 1
            assert sup.replayed == 2 and not sup.blacklist
            st = sup.stats()
            assert st["retired"] == 2 and st["generation"] == 1
        finally:
            sup.close()

    def test_single_suspect_retires_error_exactly_once(self, small_model):
        """A crash blamed on one resident uid blacklists it immediately:
        it retires "error" exactly once, carrying the crash detail, while
        its co-resident replays bit-identical."""
        jobs = [([5, 9, 17, 2], SamplingParams(max_new_tokens=16, seed=3)),
                ([1, 2, 3], SamplingParams(max_new_tokens=8, seed=4))]
        ref = _oracle(small_model, jobs)
        sup = _supervisor(small_model,
                          [FaultPlan().engine_crash("decode", 1, uid=0)])
        try:
            suspect = sup.submit(*jobs[0])
            victim = sup.submit(*jobs[1])
            assert (suspect.uid, victim.uid) == (0, 1)
            res_s = suspect.result(timeout=120)
            res_v = victim.result(timeout=120)
            assert res_s.finish_reason == "error"
            assert "engine died (generation 0)" in res_s.error
            assert "EngineCrash" in res_s.error
            assert "blacklisted as crash suspect" in res_s.error
            assert suspect.error == res_s.error  # handle carries the detail
            assert res_v.finish_reason == "length"
            assert tuple(res_v.tokens) == ref[1]
            assert sup.blacklist == {0}
            assert [r.uid for r in sup.results()].count(0) == 1  # once
            assert sup.replayed == 1
        finally:
            sup.close()

    def test_poison_request_blacklisted_on_second_strike(self, small_model):
        """Two ambiguous crashes with the same request resident: the
        repeat offender reaches blacklist_after strikes and is condemned;
        its neighbor (one strike, finished before the second crash)
        completes bit-identical."""
        poison = ([5, 9, 17, 2], SamplingParams(max_new_tokens=32, seed=5))
        victim = ([1, 2, 3], SamplingParams(max_new_tokens=4, seed=6))
        ref = _oracle(small_model, [poison, victim])
        # gen0: crash at decode #1 — both resident, ambiguous (1 strike
        # each). gen1: victim (4 tokens, decode_chunk=2) finishes by
        # decode #1; crash at #4 catches the poison alone → strike 2.
        sup = _supervisor(small_model,
                          [FaultPlan().engine_crash("decode", 1),
                           FaultPlan().engine_crash("decode", 4)],
                          blacklist_after=2)
        try:
            hp = sup.submit(*poison)
            hv = sup.submit(*victim)
            res_p = hp.result(timeout=120)
            res_v = hv.result(timeout=120)
            assert res_v.finish_reason == "length"
            assert tuple(res_v.tokens) == ref[1]
            assert res_p.finish_reason == "error"
            assert "strike 2" in res_p.error
            assert sup.blacklist == {hp.uid}
            assert sup.crash_counts[hp.uid] == 2
            # the rebuild (generation 2) lands just after the suspect's
            # retirement; the crash loop has converged and the engine idles
            assert _wait_until(lambda: sup.generation == 2)
            assert not sup.degraded
        finally:
            sup.close()

    def test_crash_before_first_token_replays_clean(self, small_model):
        """Crash at decode dispatch #0: nothing delivered yet, replay is a
        from-scratch run — the degenerate dedup case (cursor at 0)."""
        jobs = [([5, 9], SamplingParams(max_new_tokens=6, seed=7)),
                ([1, 2, 3], SamplingParams(max_new_tokens=6, seed=8))]
        ref = _oracle(small_model, jobs)
        sup = _supervisor(small_model,
                          [FaultPlan().engine_crash("decode", 0)],
                          blacklist_after=9)
        try:
            hs = [sup.submit(p, sp) for p, sp in jobs]
            assert [tuple(h.result(timeout=120).tokens) for h in hs] == ref
            assert sup.generation == 1
        finally:
            sup.close()

    def test_crash_mid_prefill_dedups_decoding_survivor(self, small_model):
        """Crash during a chunked prefill: the prefilling row is the sole
        suspect (blacklisted, "error"); the co-resident row — already
        streaming — replays with its delivered prefix deduped."""
        cfg, params = small_model
        long_prompt = list(range(1, 40))  # > prefill_chunk → chunked
        jobs = [([5, 9, 17, 2], SamplingParams(max_new_tokens=12, seed=9))]
        ref = _oracle(small_model, jobs)
        sup = _supervisor(small_model,
                          [FaultPlan().engine_crash("prefill", 3)])
        try:
            survivor = sup.submit(*jobs[0])
            # let the survivor get tokens on the wire before the suspect
            # prompt starts prefilling (its chunked prefill then crashes)
            assert _wait_until(lambda: len(survivor.output) >= 2)
            suspect = sup.submit(long_prompt,
                                 SamplingParams(max_new_tokens=12, seed=10))
            res_s = suspect.result(timeout=120)
            res_v = survivor.result(timeout=120)
            assert res_s.finish_reason == "error"
            assert "blacklisted" in res_s.error
            assert res_v.finish_reason == "length"
            assert tuple(res_v.tokens) == ref[0]
            assert sup.blacklist == {suspect.uid}
        finally:
            sup.close()


# ---------------------------------------------------------------------------
# SSE continuity across a crash (the wire-level dedup assertion)
# ---------------------------------------------------------------------------

class TestHttpRecovery:
    def test_sse_stream_continues_across_crash(self, small_model):
        jobs = [([5, 9, 17, 2], SamplingParams(max_new_tokens=10, seed=0)),
                ([1, 2, 3], SamplingParams(max_new_tokens=10, seed=1))]
        ref = _oracle(small_model, jobs)
        sup = _supervisor(small_model,
                          [FaultPlan().engine_crash("decode", 2)],
                          blacklist_after=9)
        srv = ThreadedHttpServer(sup).start()
        base = f"http://{srv.host}:{srv.port}"
        try:
            outs = [None, None]

            def fire(i):
                p, sp = jobs[i]
                outs[i] = _sse(base, {
                    "prompt": list(p), "stream": True,
                    "max_new_tokens": sp.max_new_tokens, "seed": sp.seed})

            ths = [threading.Thread(target=fire, args=(i,)) for i in (0, 1)]
            for th in ths:
                th.start()
            for th in ths:
                th.join(timeout=120)
            assert all(o is not None for o in outs)
            for i, (tokens, result) in enumerate(outs):
                assert result["finish_reason"] == "length"
                assert tuple(tokens) == ref[i]  # no dup, no gap, no drift
            assert sup.generation == 1
        finally:
            srv.stop()
            sup.close()

    def test_unsupervised_crash_maps_to_500_with_detail(self, small_model):
        """Without a supervisor the driver retires everything "error" and
        the HTTP layer maps it to 500 — the body carries the exception
        detail so a client can tell engine death from a request fault."""
        cfg, params = small_model
        eng = ServingEngine(
            params, cfg, EngineConfig(**ECFG),
            injector=FaultInjector(FaultPlan().engine_crash("decode", 0)))
        driver = EngineDriver(eng).start()
        srv = ThreadedHttpServer(driver).start()
        base = f"http://{srv.host}:{srv.port}"
        try:
            status, _h, body = _post(base, {"prompt": [1, 2, 3],
                                            "max_new_tokens": 4})
            assert status == 500
            assert "engine died (generation 0)" in body["error"]
            assert "EngineCrash" in body["error"]
        finally:
            srv.stop()
            driver.close()

    def test_degraded_sheds_503_with_retry_after(self, small_model):
        """Breaker open: new submits shed 503 + Retry-After while the
        supervisor keeps converging; /healthz reports the state."""
        sup = _supervisor(small_model,
                          [FaultPlan().engine_crash("decode", 1),
                           FaultPlan().engine_crash("decode", 0)],
                          max_restarts=2, crash_window_s=300.0,
                          retry_after_s=7.0, blacklist_after=9)
        srv = ThreadedHttpServer(sup).start()
        base = f"http://{srv.host}:{srv.port}"
        try:
            # two co-residents: both crashes attribute ambiguously, so the
            # work replays through both and lands on generation 2 — while
            # the second crash inside the window opens the breaker
            hs = [sup.submit([5, 9, 17], SamplingParams(max_new_tokens=8,
                                                        seed=0)),
                  sup.submit([1, 2, 3], SamplingParams(max_new_tokens=8,
                                                       seed=1))]
            for h in hs:
                assert h.result(timeout=120).finish_reason == "length"
            assert _wait_until(lambda: sup.degraded)
            assert sup.restarts == 2  # breaker capped the rebuild count
            status, headers, body = _post(base, {"prompt": [1, 2],
                                                 "max_new_tokens": 2})
            assert status == 503
            assert headers.get("Retry-After") == "7"
            assert body["degraded"] is True
            assert "degraded" in body["error"]
            status, _h, health = _post(base, None, path="/healthz",
                                       method="GET")
            assert health["supervisor"]["degraded"] is True
            assert health["supervisor"]["restarts"] == 2
        finally:
            srv.stop()
            sup.close()


# ---------------------------------------------------------------------------
# watchdog: a hung step is a crash
# ---------------------------------------------------------------------------

class TestWatchdog:
    def test_hung_step_recovers_and_replays(self, small_model):
        """stall_step wedges the driver thread inside engine.step() after
        advancing the (virtual) engine clock past the watchdog budget:
        the supervisor reaps the wedged driver, rebuilds, and replays;
        when the stalled thread finally wakes it finds itself abandoned
        and exits without touching the migrated handles."""
        jobs = [([5, 9, 17, 2], SamplingParams(max_new_tokens=8, seed=11)),
                ([1, 2, 3], SamplingParams(max_new_tokens=8, seed=12))]
        ref = _oracle(small_model, jobs)
        clock = VirtualClock()
        sup = _supervisor(small_model,
                          [FaultPlan().stall_step(at_step=3, hang_s=60.0)],
                          clocks=[clock],
                          watchdog_step_timeout_s=5.0,
                          blacklist_after=9)
        try:
            hs = [sup.submit(p, sp) for p, sp in jobs]
            inj = sup._injectors[0]
            assert inj.stall_engaged.wait(timeout=60)
            # the generation id moves before the recovery record lands
            assert _wait_until(lambda: sup.recoveries)
            assert sup.generation == 1
            rec = sup.recoveries[0]
            assert rec["exc"].startswith("StepTimeout")
            inj.release_stalls()  # the wedged gen-0 thread wakes, exits
            assert [tuple(h.result(timeout=120).tokens) for h in hs] == ref
            assert sup.replayed == 2
            # the woken thread must not have double-delivered anything
            assert all(len(h.output) == 8 for h in hs)
        finally:
            for inj in sup._injectors:
                inj.release_stalls()
            sup.close()


# ---------------------------------------------------------------------------
# breaker lifecycle + terminal factory failure
# ---------------------------------------------------------------------------

class TestBreaker:
    def test_breaker_closes_after_quiet_window(self, small_model):
        sup = _supervisor(small_model,
                          [FaultPlan().engine_crash("decode", 0)],
                          max_restarts=1, crash_window_s=0.2,
                          blacklist_after=9)
        try:
            # two co-residents: the crash attributes ambiguously, so both
            # replay (a lone resident would be condemned as sole suspect)
            hs = [sup.submit([1, 2, 3], SamplingParams(max_new_tokens=4,
                                                       seed=0)),
                  sup.submit([4, 5], SamplingParams(max_new_tokens=4,
                                                    seed=1))]
            for h in hs:
                assert h.result(timeout=120).finish_reason == "length"
            assert _wait_until(lambda: sup.restarts == 1)
            # opened by the crash, closed by a crash-free window
            assert _wait_until(lambda: not sup.degraded)
            h2 = sup.submit([4, 5], SamplingParams(max_new_tokens=2, seed=1))
            assert h2.result(timeout=120).finish_reason == "length"
        finally:
            sup.close()

    def test_factory_failure_is_terminal(self, small_model):
        cfg, params = small_model
        built = {"n": 0}

        def factory():
            if built["n"] >= 1:
                raise RuntimeError("no artifact to rebuild from")
            built["n"] += 1
            return ServingEngine(
                params, cfg, EngineConfig(**ECFG),
                injector=FaultInjector(
                    FaultPlan().engine_crash("decode", 0)))

        sup = EngineSupervisor(factory, restart_backoff_s=0.01).start()
        try:
            h = sup.submit([1, 2, 3], SamplingParams(max_new_tokens=4))
            res = h.result(timeout=120)
            assert res.finish_reason == "error"
            assert _wait_until(lambda: sup.dead)
            with pytest.raises(DegradedError, match="permanently failed"):
                sup.submit([4, 5], SamplingParams(max_new_tokens=2))
            assert sup.supervisor_status()["dead"] is True
        finally:
            sup.close()


# ---------------------------------------------------------------------------
# drain/close vs crash races
# ---------------------------------------------------------------------------

class TestShutdownRaces:
    def test_drain_racing_a_crash_never_hangs(self, small_model):
        jobs = [([5, 9, 17, 2], SamplingParams(max_new_tokens=8, seed=13)),
                ([1, 2, 3], SamplingParams(max_new_tokens=8, seed=14))]
        ref = _oracle(small_model, jobs)
        sup = _supervisor(small_model,
                          [FaultPlan().engine_crash("decode", 1)],
                          blacklist_after=9)
        try:
            hs = [sup.submit(p, sp) for p, sp in jobs]
            # wait until both requests are resident (a drain would shed
            # fair-queue waiters with "rejected"), then drain while the
            # crash is (about to be) in flight: reap sets the old driver's
            # drained event, so this returns rather than deadlocking; the
            # replay then finishes on the new generation
            assert _wait_until(lambda: all(h._delivered > 0 for h in hs))
            assert sup.drain(timeout=60.0)
            assert [tuple(h.result(timeout=120).tokens) for h in hs] == ref
        finally:
            sup.close()

    def test_close_is_idempotent_after_crash(self, small_model):
        sup = _supervisor(small_model,
                          [FaultPlan().engine_crash("decode", 0)],
                          blacklist_after=9)
        hs = [sup.submit([1, 2, 3], SamplingParams(max_new_tokens=4,
                                                   seed=0)),
              sup.submit([4, 5], SamplingParams(max_new_tokens=4, seed=1))]
        for h in hs:
            assert h.result(timeout=120).finish_reason == "length"
        sup.close()
        sup.close()  # second close is a no-op, not an error

    def test_unsupervised_driver_close_after_fatal(self, small_model):
        """Standalone driver: _fatal retires everything with the crash
        detail; drain() and double close() afterwards are no-ops."""
        cfg, params = small_model
        eng = ServingEngine(
            params, cfg, EngineConfig(**ECFG),
            injector=FaultInjector(FaultPlan().engine_crash("decode", 0)))
        driver = EngineDriver(eng).start()
        h = driver.submit([1, 2, 3], SamplingParams(max_new_tokens=4))
        res = h.result(timeout=120)
        assert res.finish_reason == "error"
        assert "engine died (generation 0)" in res.error
        assert h.error == res.error
        assert driver.fatal_exc is not None
        assert driver.drain(timeout=10.0)
        driver.close()
        driver.close()


# ---------------------------------------------------------------------------
# heartbeat schema 3: generation + restarts ride the fleet protocol
# ---------------------------------------------------------------------------

class TestHeartbeat:
    def test_heartbeat_carries_generation_and_restarts(self, small_model,
                                                       tmp_path):
        sup = _supervisor(small_model,
                          [FaultPlan().engine_crash("decode", 0)],
                          blacklist_after=9)
        try:
            hs = [sup.submit([1, 2, 3], SamplingParams(max_new_tokens=4,
                                                       seed=0)),
                  sup.submit([4, 5], SamplingParams(max_new_tokens=4,
                                                    seed=1))]
            for h in hs:
                assert h.result(timeout=120).finish_reason == "length"
            digest = sup.call(lambda eng: eng.obs.digest())
            assert digest["engine_generation"] == 1
            assert digest["engine_restarts"] == 1
            snap = sup.call(lambda eng: eng.health())
            snap.beat(HeartbeatMonitor(str(tmp_path)), metrics=digest)
        finally:
            sup.close()
        beats = StragglerDetector(str(tmp_path)).read()
        assert beats[0]["engine_generation"] == 1
        assert beats[0]["engine_restarts"] == 1

    def test_detector_tolerates_pre_supervision_payloads(self, tmp_path):
        d = tmp_path / "heartbeats"
        d.mkdir()
        (d / "host0000.json").write_text(json.dumps(
            {"host": 0, "t": 1.0, "step": 3}))  # v1: no supervision keys
        beats = StragglerDetector(str(tmp_path)).read()
        assert beats[0]["engine_generation"] == 0
        assert beats[0]["engine_restarts"] == 0


# ---------------------------------------------------------------------------
# serve.py: flag validation + second-signal force quit (subprocess)
# ---------------------------------------------------------------------------

def test_serve_supervise_requires_http():
    from repro.launch import serve
    with pytest.raises(SystemExit):
        serve.main(["--supervise"])


@pytest.mark.slow
def test_serve_second_sigint_force_quits_nonzero(tmp_path):
    """First SIGINT drains gracefully (rc 0, covered elsewhere); a second
    one force-quits immediately with rc 128+SIGINT = 130, so a process
    manager can tell a forced kill from a clean shutdown."""
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.serve", "--no-quantize",
         "--requests", "8", "--max-new", "500", "--slots", "2"],
        cwd=ROOT, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, env={**os.environ, "PYTHONPATH": str(ROOT / "src"),
                        "PYTHONUNBUFFERED": "1"})
    try:
        booted = False
        for line in proc.stdout:
            if line.startswith("[serve] boot"):
                booted = True
                break
        assert booted, "serve.py never finished booting"
        proc.send_signal(signal.SIGINT)
        forced = False
        for line in proc.stdout:
            if "draining" in line:          # first signal acknowledged,
                proc.send_signal(signal.SIGINT)  # now really mean it
            if "force quit" in line:
                forced = True
                break
        rc = proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert forced, "second signal never hit the force-quit handler"
    assert rc == 128 + signal.SIGINT, rc
