"""Cells, configurations, traffic mixes and per-layer metrics are found by
name: a new one is new files plus a BENCHMARK.json entry, and no edit."""

from __future__ import annotations

import json
import shutil
import subprocess
import sys
from types import SimpleNamespace

import pytest

import bench_tiny_tree as tiny

from harness import spec


def _copy_tree(dest):
    shutil.copy(tiny.ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    shutil.copytree(tiny.BENCH, dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".out",
                                                  "tests"))
    return dest / "bench"


def test_every_cell_resolves():
    top = json.loads((tiny.ROOT / "BENCHMARK.json").read_text())
    for w in top["workloads"]:
        cell = spec.load_cell(tiny.ROOT, w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.reference().final_hidden
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in names
            assert callable(cell.reader(m["name"]))


def test_new_cell_traffic_and_metric_are_files_only(tmp_path):
    bench = _copy_tree(tmp_path)
    before = {p.relative_to(bench): p.read_bytes()
              for p in bench.rglob("*") if p.is_file()}
    t = json.loads((bench / "traffic" / "chat.json").read_text())
    t["arrivals"]["rate_per_s"] = 0.5
    (bench / "traffic" / "chat-slow.json").write_text(json.dumps(t))
    c = json.loads((bench / "configs" / "qwen2-1.5b.json").read_text())
    c["name"] = "qwen2-1.5b-int8kv"
    c["quantization"]["kv_cache_dtype"] = "int8"
    (bench / "configs" / "qwen2-1.5b-int8kv.json").write_text(json.dumps(c))
    (bench / "metrics" / "engine.slots.py").write_text(
        "def read(run):\n    return run.max_slots\n")
    top = json.loads((tmp_path / "BENCHMARK.json").read_text())
    top["configs"].append({"name": "qwen2-1.5b-int8kv",
                           "source": "https://huggingface.co/Qwen/Qwen2-1.5B",
                           "file": "bench/configs/qwen2-1.5b-int8kv.json",
                           "reduced": [], "why": "int8 ring"})
    top["workloads"].append({"name": "qwen2-1.5b-int8kv.chat-slow",
                             "config": "qwen2-1.5b-int8kv",
                             "traffic": "chat-slow", "chips": 1, "why": "x"})
    top["per_layer"].append({"name": "engine.slots", "unit": "1",
                             "better": "higher", "source": "program_counter",
                             "layer": "engine", "moves": "ttft_p90_ms",
                             "workloads": ["qwen2-1.5b-int8kv.chat-slow"]})
    for m in top["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("qwen2-1.5b-int8kv.chat-slow")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(top))

    cell = spec.load_cell(tmp_path, "qwen2-1.5b-int8kv.chat-slow",
                          bench_dir=bench)
    assert cell.traffic["arrivals"]["rate_per_s"] == 0.5
    assert cell.config["quantization"]["kv_cache_dtype"] == "int8"
    assert [m["name"] for m in cell.per_layer] == ["engine.slots"]
    assert cell.reader("engine.slots")(SimpleNamespace(max_slots=64)) == 64
    after = {p.relative_to(bench): p.read_bytes()
             for p in bench.rglob("*") if p.is_file()}
    assert all(after[k] == v for k, v in before.items())


def test_unknown_cell_is_refused():
    with pytest.raises(spec.SpecError):
        spec.load_cell(tiny.ROOT, "no-such-cell")


@pytest.mark.parametrize("tree", ["checkout", "benchmark_only"])
def test_run_without_a_chip_exits_nonzero_and_prints_nothing(tree, tmp_path):
    """On the CPU (and in a directory holding only BENCHMARK.json and the
    benchmark's files) the run refuses before any work."""
    root = tiny.ROOT
    if tree == "benchmark_only":
        _copy_tree(tmp_path)
        root = tmp_path
    proc = subprocess.run(
        [sys.executable, str(root / "bench" / "run.py"), "--workload",
         "qwen2-1.5b.chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
