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


# a made-up architecture, as a configuration PR would add one: a GQA
# decoder whose first layer is a dense MLP and whose others are experts
# behind a router (the program's prefix and "attn+moe" blocks)
TOY_ARCH = '''"""A made-up dense-then-experts GQA decoder (test only)."""

from harness import work
from harness.weights import signed

SHRINK = {}
KERNELS = {"chunk_attention": ("chunk_attention",),
           "moe_dispatch": ("moe_dispatch",)}
LEAF_RULES = {"kernel": lambda k, s, p: signed(k, s, -12)}   # the router


def model_config(c):
    from repro.models.moe import MoEConfig

    return dict(
        family="moe", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        prefix_pattern=("attn+mlp",) * c["first_k_dense_replace"],
        block_pattern=("attn+moe",),
        moe=MoEConfig(n_experts=c["n_routed_experts"],
                      top_k=c["num_experts_per_tok"],
                      d_expert=c["moe_intermediate_size"],
                      n_shared=c["n_shared_experts"], capacity_factor=-1.0))


def layer(model, i):
    c = model.config
    d, heads = c["hidden_size"], c["num_attention_heads"]
    kv, hd = c["num_key_value_heads"], d // heads
    mats = [(d, heads * hd), (d, kv * hd), (d, kv * hd), (heads * hd, d)]
    kernels = {"chunk_attention": lambda rows: work.attention(
        model, rows, heads, kv, hd)}
    if i < c["first_k_dense_replace"]:
        ff = c["intermediate_size"]
        return work.Layer(mats + [(d, ff), (d, ff), (ff, d)], 4 * d,
                          kernels=kernels)
    e, fe = c["n_routed_experts"], c["moe_intermediate_size"]
    share, sh = c["num_experts_per_tok"] / e, c["n_shared_experts"] * fe
    kernels["moe_dispatch"] = lambda rows: (
        2.0 * sum(r.n for r in rows) * d * e, 4.0 * d * e)
    return work.Layer(mats + [(d, fe, share), (d, fe, share), (fe, d, share)]
                      * e + [(d, sh), (d, sh), (sh, d)], 4 * d + 4 * d * e,
                      kernels=kernels)
'''
TOY_REF = '''"""Stand-in reference of the made-up architecture: it draws leaves
as a reference would, and runs no forward pass."""


def head(c, w):
    return w.matrix("/lm_head/kernel", -1, c["hidden_size"],
                    c["vocab_size"], c["quantization"]["group_size"])


def router(c, w, layer):
    return w.leaf("/blocks/b0/moe/router/kernel", layer,
                  (c["hidden_size"], c["n_routed_experts"]), "float32")


def expert_wi(c, w, layer, e):
    return w.matrix("/blocks/b0/moe/experts/wi/kernel", layer,
                    c["hidden_size"], c["moe_intermediate_size"],
                    c["quantization"]["group_size"], index=e)


def final_hidden(c, w, tokens, fp8=False):
    raise NotImplementedError("a stand-in: the test serves nothing")
'''
TOY_CONFIG = {
    "name": "toy-moe", "source": "made up for a test", "model_type": "toy_moe",
    "hidden_size": 128, "intermediate_size": 384, "moe_intermediate_size": 128,
    "n_routed_experts": 4, "num_experts_per_tok": 2, "n_shared_experts": 1,
    "first_k_dense_replace": 1, "num_attention_heads": 2,
    "num_key_value_heads": 1, "num_hidden_layers": 3, "vocab_size": 512,
    "rms_norm_eps": 1e-6, "rope_theta": 10000.0, "torch_dtype": "float32",
    "quantization": {"group_size": 128, "alpha_dtype": "float32"},
    "engine": {"max_slots": 4, "capacity": 256, "prefill_chunk": 8,
               "decode_chunk": 4, "kv_layout": "ring"},
    "reference": "ref_toy_moe.py",
    "correct": {"max_logit_gap": 1e-3, "sample_requests": 2,
                "tokens_per_request": 8, "tokens_to_compare": 16}}


def test_new_architecture_is_files_only(tmp_path):
    """A model_type the harness has never seen enters as new files (its
    configuration, architecture module and reference) and list entries:
    its own leaf rule, its own kernel and a first layer unlike the rest
    all reach the harness, and no file that was there changes."""
    import jax.numpy as jnp
    import numpy as np

    from harness import model, trace, weights, work
    from repro.core.packing import unpack_trits

    bench = _copy_tree(tmp_path)
    before = {p.relative_to(bench): p.read_bytes()
              for p in bench.rglob("*") if p.is_file()}
    (bench / "configs" / "toy-moe.json").write_text(json.dumps(TOY_CONFIG))
    (bench / "configs" / "arch_toy_moe.py").write_text(TOY_ARCH)
    (bench / "configs" / "ref_toy_moe.py").write_text(TOY_REF)
    top = json.loads((tmp_path / "BENCHMARK.json").read_text())
    top["configs"].append({"name": "toy-moe", "source": "made up",
                           "file": "bench/configs/toy-moe.json",
                           "reduced": [], "why": "a test"})
    top["workloads"].append({"name": "toy-moe.chat", "config": "toy-moe",
                             "traffic": "chat", "chips": 1, "why": "x"})
    for m in top["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("toy-moe.chat")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(top))

    cell = spec.load_cell(tmp_path, "toy-moe.chat", bench_dir=bench)
    c, arch = cell.config, cell.arch
    mcfg = model.model_config(c, arch)
    assert mcfg.prefix_pattern == ("attn+mlp",)
    assert mcfg.moe.n_experts == 4

    # the program's matrices, layer by layer, are what the work counts
    per_layer, rest = tiny.quantized_matrices(c, arch)
    layers = work.Model(c, arch).layers
    assert per_layer == [sorted(m[:2] for m in layer.matrices)
                         for layer in layers]
    assert per_layer[0] != per_layer[1] == per_layer[2]
    assert rest == [(128, 512)]
    out = work.dispatch(work.Model(c, arch), [[work.Row(0, 8, 1)]],
                        {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0})
    assert list(out) == ["ternary_matmul", "chunk_attention", "moe_dispatch",
                         "other"]
    assert out["moe_dispatch"].calls == 2 and out["chunk_attention"].calls == 3
    # 8 rows; q, o are 128 x 128 and k, v 128 x 64 (one kv head of 64);
    # each routed expert's three matrices see 8 x 2 / 4 of the rows
    attn = 2.0 * 8 * 128 * (2 * 128 + 2 * 64)
    dense, shared = 2.0 * 8 * 128 * 3 * 384, 2.0 * 8 * 128 * 3 * 128
    expert = 2.0 * 4 * 128 * 3 * 128
    head = 2.0 * 1 * 128 * 512
    assert out["ternary_matmul"].flops == (
        3 * attn + dense + 2 * (shared + 4 * expert) + head)

    # program and reference draw the same leaves, the router by its own rule
    seed = 2 ** 33 + 5
    rules = weights.leaf_rules(arch)
    params = weights.program_params(mcfg, seed, 128, rules)
    w = weights.Seeded(seed, rules)
    ref = cell.reference()
    moe = params["blocks"]["b0"]["moe"]
    for layer in range(2):
        np.testing.assert_array_equal(moe["router"]["kernel"][layer],
                                      ref.router(c, w, layer))
        wi = moe["experts"]["wi"]["kernel"]
        for e in range(4):
            t1, t2 = (unpack_trits(t[layer, e], jnp.int8)
                      for t in (wi.t1p, wi.t2p))
            np.testing.assert_array_equal(
                weights.dequantized(t1, t2, wi.alpha[layer, e], 128),
                ref.expert_wi(c, w, layer, e))
    assert not np.array_equal(ref.expert_wi(c, w, 0, 0),
                              ref.expert_wi(c, w, 0, 1))
    assert ref.head(c, w).shape == (128, 512)

    # an op carrying the new mark is the new kernel's
    kernels = trace.kernel_set([arch])
    op = "%moe_dispatch.3 = f32[8,128]{1,0} custom-call(f32[8,128] %x)"
    assert trace.kernel_of(op, kernels) == "moe_dispatch"
    assert trace.kernel_of(op, trace.kernel_set([])) is None

    after = {p.relative_to(bench): p.read_bytes()
             for p in bench.rglob("*") if p.is_file()}
    assert all(after[k] == v for k, v in before.items())


def test_missing_architecture_is_refused(tmp_path):
    bench = _copy_tree(tmp_path)
    path = bench / "configs" / "qwen2-1.5b.json"
    c = json.loads(path.read_text())
    c["model_type"] = "no_such_type"
    path.write_text(json.dumps(c))
    with pytest.raises(spec.SpecError, match="arch_no_such_type"):
        spec.load_cell(tmp_path, "qwen2-1.5b.chat", bench_dir=bench)


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
