"""The comparison that decides ``correct``, driven end to end at test size.

Each case runs the harness's whole run (set-up, a short window through the
driver, the reference) on the CPU with the chip check left out, once as
the program is and once with the timed path broken underneath: a token
altered where it is produced, and a decode step that returns its state
unchanged. The float8 control is read from the sound run and judged by
the run's own rule, which has to find it not correct.
"""

from __future__ import annotations

import time

import pytest

import bench_tiny_tree as tiny  # puts the harness and the program on sys.path

CELLS = [w["name"] for w in tiny.top()["workloads"]]


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_tree")
    return root, tiny.tiny_tree(root)


def _run(tree, cell_name, control=False):
    import jax

    from harness import session, spec

    root, bench = tree
    cell = spec.load_cell(root, cell_name, bench_dir=bench)
    return session.run_cell(cell, 2 ** 31 + 12345, 1.5, False,
                            time.perf_counter(), devices=jax.devices()[:1],
                            control=control)


def _compared(line):
    """The gap statistics the cell compares, with their limits."""
    return {k: v["limit"] for k, v in line["checks"].items()
            if k.endswith("_logit_gap")}


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_control_is_not(tree, cell):
    line = _run(tree, cell, control=True)
    compared = _compared(line)
    assert compared
    assert line["correct"], line["checks"]
    assert line["failed"] == 0
    for k, limit in compared.items():
        assert line["checks"][k]["value"] <= limit
        assert line["control"]["program"][k] == line["checks"][k]["value"]
    assert any(line["control"]["control"][k] > limit
               for k, limit in compared.items())
    assert line["control"]["correct"] is False
    assert line["checks"]["requests_compared"]["value"] \
        >= line["checks"]["requests_compared"]["limit"] > 1
    assert list(line)[-2:] == ["checks", "control"]


@pytest.mark.parametrize("cell", CELLS)
def test_altered_token_is_not_correct(tree, cell, monkeypatch):
    from repro.serving import engine as engine_mod

    real = engine_mod.sample_tokens_per_request

    def altered(logits, *a, **k):
        return (real(logits, *a, **k) + 1) % logits.shape[-1]

    monkeypatch.setattr(engine_mod, "sample_tokens_per_request", altered)
    line = _run(tree, cell)
    assert not line["correct"]
    assert all(line["checks"][k]["value"] > limit
               for k, limit in _compared(line).items())


@pytest.mark.parametrize("cell", CELLS)
def test_state_left_unchanged_is_not_correct(tree, cell, monkeypatch):
    from repro.serving import engine as engine_mod

    real = engine_mod.decode_step

    def frozen(params, cfg, state, tokens, active=None):
        logits, _ = real(params, cfg, state, tokens, active)
        return logits, state

    monkeypatch.setattr(engine_mod, "decode_step", frozen)
    line = _run(tree, cell)
    assert not line["correct"]
