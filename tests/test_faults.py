"""Fault-contained serving: deadlines, load shedding, quarantine, and the
deterministic fault-injection harness (repro.serving.faults).

The keystone assertion, repeated across scenarios: whatever the plan does
to other requests — NaN logits, dispatch exceptions, deadline expiry,
shedding — requests the plan does *not* touch finish bit-identical to a
fault-free run."""

import jax
import pytest

from repro import configs
from repro.artifacts import ArtifactError, load_artifact, verify_artifact
from repro.models import init_params
from repro.serving import (EngineConfig, FaultInjector, FaultPlan,
                           SamplingParams, SerialAdmitEngine, ServingEngine,
                           VirtualClock)
from repro.serving.faults import (corrupt_artifact_shard,
                                  truncate_artifact_shard)


@pytest.fixture(scope="module")
def small_model():
    cfg = configs.get_smoke_config("qwen2-1.5b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def solo_ref(small_model, prompt, sp):
    cfg, params = small_model
    eng = ServingEngine(params, cfg, EngineConfig(max_slots=1, capacity=32))
    return eng.submit(prompt, sp).result().tokens


def timed_engine(small_model, ecfg=None, plan=None):
    """Engine on a VirtualClock (tests never sleep)."""
    cfg, params = small_model
    clock = VirtualClock()
    inj = FaultInjector(plan or FaultPlan(), clock=clock)
    eng = ServingEngine(params, cfg,
                        ecfg or EngineConfig(max_slots=2, capacity=32),
                        injector=inj)
    return eng, clock


class TestDeadlines:
    def test_deadline_expires_mid_decode(self, small_model):
        """A resident request past deadline_s retires with "timeout" at the
        next step, keeping the tokens it already produced; its co-batched
        neighbor is bit-unperturbed."""
        sp = SamplingParams(max_new_tokens=8, temperature=0.9, seed=41)
        ref = solo_ref(small_model, [5, 9, 17, 2], sp)

        eng, clock = timed_engine(small_model, EngineConfig(
            max_slots=2, capacity=32, decode_chunk=2))
        keeper = eng.submit([5, 9, 17, 2], sp)
        victim = eng.submit([1, 2], SamplingParams(max_new_tokens=64,
                                                   deadline_s=10.0))
        eng.step()
        eng.step()
        assert victim.output and not victim.done  # genuinely mid-decode
        got = len(victim.output)
        clock.advance(11.0)
        eng.step()  # sweep fires before this step's work
        assert victim.finish_reason == "timeout"
        assert len(victim.output) == got  # kept what it had
        assert victim.t_done == clock()
        assert keeper.result().tokens == ref
        assert eng.timeouts == 1

    def test_ttft_deadline_expires_queued_request(self, small_model):
        """A queued request that misses its first-token budget never
        admits; one that produced token 0 in time is no longer bound by
        ttft_deadline_s."""
        eng, clock = timed_engine(small_model)
        fast = eng.submit([1, 2, 3], SamplingParams(max_new_tokens=6,
                                                    ttft_deadline_s=5.0))
        eng.submit([4, 5], SamplingParams(max_new_tokens=6))
        late = eng.submit([6, 7], SamplingParams(max_new_tokens=2,
                                                 ttft_deadline_s=5.0))
        eng.step()  # both slots busy; `late` waits
        assert fast.output  # first token landed inside the budget
        clock.advance(6.0)
        done = eng.run()
        assert late.finish_reason == "timeout" and late.output == []
        assert late in done
        assert fast.finish_reason == "length"  # ttft satisfied, no deadline
        assert len(fast.output) == 6

    def test_deadline_frees_slot_for_next_admission(self, small_model):
        eng, clock = timed_engine(small_model, EngineConfig(max_slots=1,
                                                            capacity=32))
        stuck = eng.submit([1, 2], SamplingParams(max_new_tokens=64,
                                                  deadline_s=1.0))
        nxt = eng.submit([3, 4], SamplingParams(max_new_tokens=3))
        eng.step()
        clock.advance(2.0)
        eng.step()  # sweep retires `stuck`; same step admits `nxt`
        assert stuck.finish_reason == "timeout"
        assert eng.admits == 2  # `nxt` reused the freed slot that same step
        assert len(nxt.result().tokens) == 3

    def test_stall_clock_fault_is_deterministic(self, small_model):
        """FaultPlan.stall_clock expires a deadline at an exact engine
        step, twice over."""
        cfg, params = small_model
        reasons = []
        for _ in range(2):
            inj = FaultInjector(FaultPlan().stall_clock(at_step=2,
                                                        advance_s=60.0),
                                clock=VirtualClock())
            eng = ServingEngine(params, cfg,
                                EngineConfig(max_slots=2, capacity=32),
                                injector=inj)
            h = eng.submit([1, 2, 3], SamplingParams(max_new_tokens=32,
                                                     deadline_s=30.0))
            eng.run()
            reasons.append((h.finish_reason, len(h.output)))
            assert inj.log and inj.log[0][0] == "stall"
        assert reasons[0] == reasons[1]
        assert reasons[0][0] == "timeout"


class TestAdmissionControl:
    def test_reject_policy_sheds_past_queue_cap(self, small_model):
        cfg, params = small_model
        eng = ServingEngine(params, cfg, EngineConfig(
            max_slots=1, capacity=32, max_queue=1,
            admission_policy="reject"))
        a = eng.submit([1, 2], SamplingParams(max_new_tokens=2))
        eng.step()  # `a` admits into the slot; the queue is free again
        b = eng.submit([3, 4], SamplingParams(max_new_tokens=2))
        shed = eng.submit([5, 6], SamplingParams(max_new_tokens=2))
        assert shed.finish_reason == "rejected" and shed.done
        assert "queue full" in shed.error
        assert shed.result().error == shed.error  # surfaced in the record
        eng.run()
        assert a.finish_reason == b.finish_reason == "length"
        assert eng.sheds == 1

    def test_resident_token_cap_sheds(self, small_model):
        cfg, params = small_model
        eng = ServingEngine(params, cfg, EngineConfig(
            max_slots=2, capacity=32, max_resident_tokens=20))
        eng.submit([1, 2, 3], SamplingParams(max_new_tokens=8))  # 11 tokens
        shed = eng.submit([4, 5], SamplingParams(max_new_tokens=16))  # +18
        assert shed.finish_reason == "rejected"
        assert "resident-token" in shed.error
        ok = eng.submit([4, 5], SamplingParams(max_new_tokens=4))  # +6 fits
        eng.run()
        assert ok.finish_reason == "length"

    def test_block_policy_waits_for_drain(self, small_model):
        """Under "block", an over-cap submit drives step() until the fleet
        drains — the handle returns admissible, nothing is shed."""
        cfg, params = small_model
        eng = ServingEngine(params, cfg, EngineConfig(
            max_slots=1, capacity=32, max_resident_tokens=6,
            admission_policy="block"))
        a = eng.submit([1, 2], SamplingParams(max_new_tokens=2))  # 4 committed
        b = eng.submit([3, 4], SamplingParams(max_new_tokens=2))  # 4 more > 6
        # submit(b) could only return once `a` fully left residency
        assert a.done and not b.done
        eng.run()
        assert b.finish_reason == "length" and eng.sheds == 0

    def test_never_fits_rejected_even_under_block(self, small_model):
        cfg, params = small_model
        eng = ServingEngine(params, cfg, EngineConfig(
            max_slots=1, capacity=32, max_resident_tokens=8,
            admission_policy="block"))
        h = eng.submit([1, 2, 3, 4], SamplingParams(max_new_tokens=16))
        assert h.finish_reason == "rejected"
        assert "resident-token cap" in h.error

    def test_resident_tokens_accounting(self, small_model):
        """The gauge counts clipped prompt + generation budget over
        queued + resident work and drains as requests finish."""
        cfg, params = small_model
        eng = ServingEngine(params, cfg, EngineConfig(max_slots=1,
                                                      capacity=32))
        eng.submit([1, 2, 3], SamplingParams(max_new_tokens=5))   # 8
        eng.submit([4, 5], SamplingParams(max_new_tokens=4))       # 6 queued
        assert eng.resident_tokens() == 14
        eng.run()
        assert eng.resident_tokens() == 0


class TestFaultContainment:
    def test_nan_logits_mid_decode_contained(self, small_model):
        """NaN poison at generated-token k (through the real on-device
        detection path): the victim retires with "error" after k tokens,
        the slot quarantines, the neighbor is bit-identical."""
        sp = SamplingParams(max_new_tokens=8, temperature=0.9, seed=41)
        ref = solo_ref(small_model, [5, 9, 17, 2], sp)
        cfg, params = small_model
        eng = ServingEngine(
            params, cfg, EngineConfig(max_slots=2, capacity=32,
                                      quarantine_steps=None),
            injector=FaultInjector(FaultPlan().nan_logits(uid=1,
                                                          gen_index=3)))
        keeper = eng.submit([5, 9, 17, 2], sp)        # uid 0
        victim = eng.submit([1, 2], SamplingParams(max_new_tokens=8))
        eng.run()
        assert victim.finish_reason == "error"
        assert len(victim.output) == 3  # tokens before the poisoned one
        assert "non-finite logits" in victim.error
        assert keeper.output == list(ref)
        assert list(eng.quarantined) != []

    def test_nan_at_prefill_finisher_contained(self, small_model):
        """gen_index 0 poisons the token sampled as prefill completes."""
        cfg, params = small_model
        eng = ServingEngine(
            params, cfg, EngineConfig(max_slots=2, capacity=32),
            injector=FaultInjector(FaultPlan().nan_logits(uid=0,
                                                          gen_index=0)))
        victim = eng.submit([1, 2, 3], SamplingParams(max_new_tokens=4))
        other = eng.submit([4, 5], SamplingParams(max_new_tokens=4))
        eng.run()
        assert victim.finish_reason == "error" and victim.output == []
        assert "prefill" in victim.error
        assert other.finish_reason == "length" and len(other.output) == 4

    def test_attributed_dispatch_fault_retires_one_row(self, small_model):
        """An EngineFault carrying a slot retires exactly that request;
        survivors repeat the vetoed step and stay bit-identical."""
        sp = SamplingParams(max_new_tokens=6, temperature=0.9, seed=41)
        ref = solo_ref(small_model, [5, 9, 17, 2], sp)
        cfg, params = small_model
        eng = ServingEngine(
            params, cfg,
            EngineConfig(max_slots=2, capacity=32, decode_chunk=2),
            injector=FaultInjector(
                FaultPlan().dispatch_error("decode", 1, uid=1)))
        keeper = eng.submit([5, 9, 17, 2], sp)
        victim = eng.submit([1, 2], SamplingParams(max_new_tokens=6))
        eng.run()
        assert victim.finish_reason == "error"
        assert "dispatch failed" in victim.error
        assert keeper.finish_reason == "length"
        assert keeper.output == list(ref)
        assert eng.errors == 1

    def test_unattributed_dispatch_fault_contains_whole_dispatch(
            self, small_model):
        """No slot attribution → every participating request retires (the
        honest containment unit); the engine keeps stepping and fresh work
        completes after rehabilitation."""
        cfg, params = small_model
        eng = ServingEngine(
            params, cfg,
            EngineConfig(max_slots=2, capacity=32, quarantine_steps=None),
            injector=FaultInjector(FaultPlan().dispatch_error("decode", 0)))
        a = eng.submit([1, 2, 3], SamplingParams(max_new_tokens=4))
        b = eng.submit([4, 5], SamplingParams(max_new_tokens=4))
        eng.run()
        assert a.finish_reason == b.finish_reason == "error"
        assert sorted(eng.quarantined) == [0, 1]
        # operator override: row-reset + return to pool, then serve again
        assert sorted(eng.rehabilitate()) == [0, 1]
        assert eng.quarantined == {}
        c = eng.submit([6, 7], SamplingParams(max_new_tokens=3))
        eng.run()
        assert c.finish_reason == "length"

    def test_prefill_dispatch_fault_contained(self, small_model):
        cfg, params = small_model
        eng = ServingEngine(
            params, cfg, EngineConfig(max_slots=2, capacity=32),
            injector=FaultInjector(
                FaultPlan().dispatch_error("prefill", 0)))
        a = eng.submit([1, 2, 3], SamplingParams(max_new_tokens=3))
        b = eng.submit([4, 5], SamplingParams(max_new_tokens=3))
        eng.run()
        # both rows were in the vetoed first prefill dispatch
        assert a.finish_reason == b.finish_reason == "error"
        assert eng.errors == 2

    def test_quarantine_cooldown_auto_rehabilitates(self, small_model):
        """quarantine_steps engine steps after containment, the slot
        row-resets and rejoins the pool on its own — a fully-quarantined
        engine self-heals instead of stranding queued work."""
        cfg, params = small_model
        eng = ServingEngine(
            params, cfg,
            EngineConfig(max_slots=1, capacity=32, quarantine_steps=2),
            injector=FaultInjector(FaultPlan().dispatch_error("decode", 0)))
        bad = eng.submit([1, 2], SamplingParams(max_new_tokens=4))
        queued = eng.submit([3, 4], SamplingParams(max_new_tokens=3))
        done = eng.run()
        assert bad.finish_reason == "error"
        assert queued.finish_reason == "length" and queued in done
        assert eng.quarantined == {}

    def test_serial_engine_contains_faults_too(self, small_model):
        """The PR-1 baseline implements the same containment contract."""
        cfg, params = small_model
        eng = SerialAdmitEngine(
            params, cfg, EngineConfig(max_slots=2, capacity=32),
            injector=FaultInjector(FaultPlan()
                                   .dispatch_error("prefill", 0)
                                   .nan_logits(uid=1, gen_index=0)))
        a = eng.submit([1, 2, 3], SamplingParams(max_new_tokens=4))
        b = eng.submit([4, 5], SamplingParams(max_new_tokens=4))
        c = eng.submit([6, 7, 8], SamplingParams(max_new_tokens=4))
        eng.run()
        assert a.finish_reason == "error" and b.finish_reason == "error"
        assert c.finish_reason == "length"  # self-healed via cool-down

    def test_production_engine_has_no_injection_residue(self, small_model):
        """injector=None (the default) compiles the poison path out: the
        decode jit cache never contains a use_poison=True entry."""
        cfg, params = small_model
        eng = ServingEngine(params, cfg, EngineConfig(max_slots=1,
                                                      capacity=32))
        eng.submit([1, 2, 3], SamplingParams(max_new_tokens=4))
        eng.run()
        assert all(k[3] is False for k in eng._loop_cache)


class TestHealthSnapshot:
    def test_gauges_and_counters(self, small_model):
        plan = FaultPlan().nan_logits(uid=0, gen_index=1)
        eng, clock = timed_engine(small_model, EngineConfig(
            max_slots=2, capacity=32, max_queue=3,
            quarantine_steps=None), plan)
        victim = eng.submit([1, 2], SamplingParams(max_new_tokens=8))
        eng.submit([3, 4], SamplingParams(max_new_tokens=2))
        eng.submit([5, 6], SamplingParams(max_new_tokens=2))
        shed = eng.submit([7, 8], SamplingParams(max_new_tokens=2))
        h = eng.health()
        assert h.queue_depth == 3 and h.resident == 0
        assert h.sheds == 1 and shed.finish_reason == "rejected"
        eng.run()
        h = eng.health()
        assert victim.finish_reason == "error"
        assert h.errors == 1 and h.completed == 2
        assert h.quarantined_slots != ()
        assert h.free_slots == 2 - len(h.quarantined_slots)
        assert h.resident_tokens == 0 and h.queue_depth == 0
        assert h.t == clock()
        s = h.summary()
        assert "error=1" in s and "shed=1" in s

    def test_snapshot_beats_into_fleet_monitor(self, small_model, tmp_path):
        """A serving host publishes through the training heartbeat
        protocol and shows up in the same StragglerDetector assessment."""
        from repro.runtime.monitor import HeartbeatMonitor, StragglerDetector

        cfg, params = small_model
        eng = ServingEngine(params, cfg, EngineConfig(max_slots=1,
                                                      capacity=32))
        eng.submit([1, 2], SamplingParams(max_new_tokens=2))
        eng.run()
        eng.health().beat(HeartbeatMonitor(str(tmp_path), host_id=0))
        rep = StragglerDetector(str(tmp_path)).assess()
        assert rep["healthy"] == [0]
        beat = StragglerDetector(str(tmp_path)).read()[0]
        assert beat["completed"] == 1 and beat["queue_depth"] == 0


class TestArtifactFaults:
    @pytest.fixture()
    def artifact(self, tmp_path, small_model):
        from repro.core.ptqtp import PTQTPConfig
        from repro.artifacts import write_artifact

        cfg, params = small_model
        out = tmp_path / "artifact"
        write_artifact(out, arch="qwen2-1.5b", model_cfg=cfg,
                       ptqtp_cfg=PTQTPConfig(group_size=32, t_max=5),
                       params=params)
        return out

    def test_corrupt_shard_report_names_damage(self, artifact):
        """verify="full" rejects a bit-flipped artifact and the error
        pinpoints the tensor, buffer, shard, byte range, and both crc32s."""
        dmg = corrupt_artifact_shard(artifact, seed=3)
        with pytest.raises(ArtifactError) as ei:
            load_artifact(artifact, verify="full")
        msg = str(ei.value)
        assert dmg["tensor"] in msg and dmg["buffer"] in msg
        assert dmg["shard"] in msg
        assert f"{dmg['crc32']:#010x}" in msg  # expected crc named
        assert "got" in msg                    # ...and the actual one

    def test_truncated_shard_caught_by_sizes_mode(self, artifact):
        """verify="sizes" rejects a torn shard from stat() alone."""
        dmg = truncate_artifact_shard(artifact, seed=0, drop_bytes=7)
        with pytest.raises(ArtifactError, match="truncated"):
            load_artifact(artifact, verify="sizes")
        with pytest.raises(ArtifactError, match=dmg["shard"]):
            verify_artifact(artifact, mode="sizes")

    def test_sizes_mode_passes_intact_artifact(self, artifact):
        tree, _ = load_artifact(artifact, verify="sizes")
        assert tree  # loaded; no checksum work was needed
        assert verify_artifact(artifact, mode="sizes") != {} or True

    def test_corruption_invisible_to_sizes_mode(self, artifact):
        """A bit-flip keeps sizes intact — only "full" catches it (the
        documented trade: O(#shards) stat vs full read)."""
        corrupt_artifact_shard(artifact, seed=1)
        load_artifact(artifact, verify="sizes")  # passes
        with pytest.raises(ArtifactError):
            load_artifact(artifact, verify="full")


class TestChaosScenario:
    def test_survivors_bit_identical_under_combined_faults(self,
                                                           small_model):
        """The acceptance scenario in miniature: NaN injection + dispatch
        exception + deadline expiry + 2x over-capacity admission, and every
        untouched request matches its fault-free twin bit for bit."""
        cfg, params = small_model
        prompts = [[5, 9, 17, 2], [1, 2], [3, 4, 5], [7, 8], [9, 10, 11],
                   [12, 13], [14, 15, 16], [6, 7]]
        sps = [SamplingParams(max_new_tokens=4 + (i % 3),
                              temperature=0.0 if i % 2 else 0.9,
                              seed=100 + i)
               for i in range(len(prompts))]

        def run(plan, ecfg):
            clock = VirtualClock()
            inj = FaultInjector(plan, clock=clock)
            eng = ServingEngine(params, cfg, ecfg, injector=inj)
            handles = [eng.submit(p, sp) for p, sp in zip(prompts, sps)]
            eng.run()
            return handles, eng

        base_cfg = dict(max_slots=2, capacity=32, decode_chunk=2)
        clean, _ = run(FaultPlan(), EngineConfig(**base_cfg))
        assert all(h.finish_reason == "length" for h in clean)

        plan = (FaultPlan()
                .nan_logits(uid=1, gen_index=1)
                .dispatch_error("decode", 3, uid=3)
                .stall_clock(at_step=4, advance_s=60.0))
        sps_f = list(sps)
        sps_f[5] = SamplingParams(max_new_tokens=4 + (5 % 3),
                                  temperature=0.9, seed=105,
                                  deadline_s=30.0)  # expires at the stall
        ecfg = EngineConfig(**base_cfg, max_queue=6,
                            admission_policy="reject")
        clock = VirtualClock()
        inj = FaultInjector(plan, clock=clock)
        eng = ServingEngine(params, cfg, ecfg, injector=inj)
        faulty = [eng.submit(p, sp) for p, sp in zip(prompts, sps_f)]
        eng.run()

        # touched = anything a fault, deadline, or the admission cap hit
        # (a dispatch fault that lands unattributed contains every request
        # in that dispatch — the containment unit, not a fixed uid set)
        touched = {h.uid for h in faulty
                   if h.finish_reason in ("error", "timeout", "rejected")}
        survivors = [h for h in faulty if h.uid not in touched]
        assert survivors  # the scenario must actually exercise survivors
        by_uid = {h.uid: h for h in clean}
        for h in survivors:
            assert h.finish_reason == "length"
            assert h.output == by_uid[h.uid].output, f"uid {h.uid}"
        assert faulty[1].finish_reason == "error"    # the planned NaN victim
        assert faulty[5].finish_reason == "timeout"  # expired at the stall
        assert any("dispatch failed" in (h.error or "") for h in faulty)
        assert sum(h.finish_reason == "rejected" for h in faulty) == 2
        kinds = {k for k, _ in inj.log}
        assert {"nan", "dispatch", "stall"} <= kinds


class TestServeExitCode:
    """launch/serve.py's batch path: a request lost to a contained fault
    (or a run that serves no token) must end the process with a failure,
    while fault containment keeps the engine serving the rest."""

    ARGS = ["--no-quantize", "--requests", "2", "--max-new", "4",
            "--slots", "2", "--capacity", "32"]

    @pytest.fixture
    def serve(self, monkeypatch):
        import signal

        from repro.launch import serve

        # the test process keeps its own signal handlers and compile cache
        saved = {s: signal.getsignal(s) for s in (signal.SIGINT,
                                                  signal.SIGTERM)}
        monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
        yield serve
        for s, h in saved.items():
            signal.signal(s, h)

    def test_dispatch_error_exits_nonzero(self, serve, monkeypatch, capsys):
        plan = FaultPlan(seed=0).dispatch_error("decode", 0)
        monkeypatch.setattr(
            serve, "ServingEngine",
            lambda *a, **kw: ServingEngine(
                *a, injector=FaultInjector(plan), **kw))
        with pytest.raises(SystemExit) as exc:
            serve.main(self.ARGS)
        assert exc.value.code not in (0, None)
        out = capsys.readouterr().out
        assert "ERROR request" in out and "decode dispatch failed" in out

    def test_clean_run_exits_zero(self, serve):
        results = serve.main(self.ARGS)
        assert [r.finish_reason for r in results] == ["length", "length"]
        assert all(len(r.tokens) == 4 for r in results)
