"""Runtime substrate: checkpoint atomicity/retention, preemption, stragglers."""

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.ptqtp import PTQTPConfig
from repro.core.quantize_model import QuantizedKernel, quantize_kernel
from repro.runtime.checkpoint import (CheckpointManager, latest_step,
                                      load_checkpoint, save_checkpoint)
from repro.runtime.monitor import HeartbeatMonitor, StragglerDetector
from repro.runtime.preempt import PreemptionGuard


def _tree(seed=0):
    r = np.random.default_rng(seed)
    return {
        "params": {"w": jnp.asarray(r.standard_normal((4, 8), np.float32)),
                   "b": jnp.asarray(r.standard_normal((8,), np.float32))},
        "opt": {"m": {"w": jnp.zeros((4, 8)), "b": jnp.zeros((8,))},
                "count": jnp.int32(7)},
        "step": jnp.int32(42),
    }


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        tree = _tree()
        save_checkpoint(tmp_path, 42, tree)
        step, loaded, _ = load_checkpoint(tmp_path)
        assert step == 42
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(loaded)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_quantized_kernel_roundtrip(self, tmp_path):
        w = jnp.asarray(np.random.default_rng(1)
                        .standard_normal((128, 64), np.float32))
        qk = quantize_kernel(w, PTQTPConfig(group_size=32, t_max=3))
        save_checkpoint(tmp_path, 1, {"layer": {"kernel": qk}})
        _, loaded, _ = load_checkpoint(tmp_path)
        lk = loaded["layer"]["kernel"]
        assert isinstance(lk, QuantizedKernel)
        assert (lk.d_in, lk.d_out, lk.group_size) == (128, 64, 32)
        np.testing.assert_array_equal(np.asarray(qk.t1p), lk.t1p)
        np.testing.assert_array_equal(np.asarray(qk.alpha), lk.alpha)

    def test_latest_points_to_newest(self, tmp_path):
        save_checkpoint(tmp_path, 1, _tree())
        save_checkpoint(tmp_path, 2, _tree(1))
        assert latest_step(tmp_path) == 2
        step, _, _ = load_checkpoint(tmp_path)
        assert step == 2

    def test_no_tmp_left_behind(self, tmp_path):
        save_checkpoint(tmp_path, 3, _tree())
        leftovers = [p for p in Path(tmp_path).iterdir() if ".tmp" in p.name]
        assert not leftovers

    def test_extra_metadata(self, tmp_path):
        save_checkpoint(tmp_path, 5, _tree(), extra={"rng": [1, 2]})
        _, _, extra = load_checkpoint(tmp_path)
        assert extra == {"rng": [1, 2]}

    def test_manager_retention(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), interval_steps=1, keep=2)
        for s in (1, 2, 3, 4):
            mgr.save(s, _tree(s))
        dirs = sorted(p.name for p in Path(tmp_path).glob("step_*"))
        assert dirs == ["step_00000003", "step_00000004"]
        step, _, _ = mgr.restore_latest()
        assert step == 4

    def test_should_save_interval(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), interval_steps=10)
        assert not mgr.should_save(5)
        assert mgr.should_save(10)
        assert not mgr.should_save(0)

    def test_missing_checkpoint_raises(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            load_checkpoint(tmp_path)


class TestPreemption:
    def test_programmatic_request(self):
        with PreemptionGuard() as g:
            assert not g.preempted
            g.request()
            assert g.preempted

    def test_signal_delivery(self):
        import os
        import signal

        with PreemptionGuard(signals=(signal.SIGUSR1,)) as g:
            os.kill(os.getpid(), signal.SIGUSR1)
            assert g.wait(timeout=2.0)


class TestStragglers:
    def test_detection(self, tmp_path):
        run = str(tmp_path)
        now = time.time()
        for host, (step_t, age) in enumerate([(1.0, 0), (1.1, 0),
                                              (5.0, 0), (1.0, 999)]):
            HeartbeatMonitor(run, host_id=host).beat(10, step_t)
            if age:  # backdate host 3 => dead
                p = Path(run) / "heartbeats" / f"host{host:04d}.json"
                d = json.loads(p.read_text())
                d["t"] = now - age
                p.write_text(json.dumps(d))
        rep = StragglerDetector(run, dead_after_s=120,
                                straggler_factor=2.0).assess(now=now)
        assert rep["dead"] == [3]
        assert rep["stragglers"] == [2]
        assert sorted(rep["healthy"]) == [0, 1]

    def test_empty_fleet(self, tmp_path):
        rep = StragglerDetector(str(tmp_path)).assess()
        assert rep["healthy"] == [] and rep["median_step_s"] is None
        assert rep["skewed"] == []

    def test_clock_skew_flagged_not_alive(self, tmp_path):
        """A heartbeat stamped in the future is a broken clock: the host is
        reported "skewed" — excluded from healthy (its liveness cannot be
        assessed) but also not "dead" (we have no evidence of death), and
        its step time does not pollute the fleet median."""
        run = str(tmp_path)
        now = time.time()
        for host, (step_t, skew) in enumerate([(1.0, 0), (1.2, 0),
                                               (50.0, 900)]):
            HeartbeatMonitor(run, host_id=host).beat(10, step_t)
            if skew:  # host 2's clock runs 15 minutes ahead
                p = Path(run) / "heartbeats" / f"host{host:04d}.json"
                d = json.loads(p.read_text())
                d["t"] = now + skew
                p.write_text(json.dumps(d))
        rep = StragglerDetector(run, dead_after_s=120,
                                skew_tolerance_s=5.0).assess(now=now)
        assert rep["skewed"] == [2]
        assert rep["dead"] == [] and sorted(rep["healthy"]) == [0, 1]
        # host 2's 50s step time is excluded from the median
        assert rep["median_step_s"] == pytest.approx(1.1)


class TestCompileCache:
    def test_env_var_wins_else_fixed_checkout_path(self, monkeypatch):
        """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX untouched;
        unset, the cache goes to the fixed <checkout>/.jax_cache."""
        from repro.runtime import compile_cache

        saved = {k: getattr(jax.config, k) for k in
                 ("jax_compilation_cache_dir", "jax_enable_compilation_cache")}
        try:
            monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere/cache")
            assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
            assert jax.config.jax_compilation_cache_dir == \
                saved["jax_compilation_cache_dir"]

            monkeypatch.delenv(compile_cache.ENV_VAR)
            path = compile_cache.enable_compile_cache()
            assert path == str(Path(__file__).resolve().parents[1]
                               / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
            assert jax.config.jax_enable_compilation_cache
            assert compile_cache.enable_compile_cache() == path  # fixed
        finally:
            for k, v in saved.items():
                jax.config.update(k, v)
