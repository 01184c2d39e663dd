"""Artifact-store benchmark: the quantize-once / serve-many economics.

Three sections, one JSON:

  * **write** — streaming artifact write vs in-memory ``quantize_tree`` over
    the same synthetic weight tree: wall-clock throughput and peak RSS
    growth (``ru_maxrss`` delta across the measured phase). Each path runs
    in a fresh subprocess (``--_child``) so one path's peak cannot shadow
    the other's. These are host-memory measurements: the children are
    pinned to the CPU platform, so they never contend with this process
    (or another) for an accelerator. The streaming path is measured both with fsync group
    commit (``stream``, the default: fsync every N tensors, manifest only
    advancing after the fsync) and with PR-3's per-tensor fsync
    (``stream_fsync1``) — the delta is the write path's durability
    overhead, which group commit amortizes.
  * **boot** — server time-to-first-token booting the same smoke model two
    ways: quantize-at-boot (the pre-PR-3 ``launch/serve.py`` pipeline) vs
    memory-mapped artifact boot (``--artifact``). The artifact is prepared
    outside the timed region — that is the whole point: quantization cost is
    paid once, not per server process.
  * **disk** — on-disk bytes/weight vs the paper's 0.53125 theoretical
    (Eq. 13, G=128, fp16 scales). The artifact stores fp32 scales so
    artifact boot is bit-identical to in-process quantization; the fp16
    theoretical at the same G is recorded next to it.

``PYTHONPATH=src python benchmarks/bench_artifacts.py [--quick]``

Writes benchmarks/results/BENCH_artifacts.json and mirrors it to
BENCH_artifacts.json at the repo root (the trajectory point ROADMAP.md
quotes).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))  # script mode

from benchmarks.common import save_result
from repro.core.ptqtp import PTQTPConfig

ROOT = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# RSS helper: each write path runs in its own subprocess, so the process-wide
# ru_maxrss delta across the measured phase isolates that path's peak growth
# ---------------------------------------------------------------------------

def _max_rss_kb() -> int:
    import resource

    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss


# ---------------------------------------------------------------------------
# child process: one write path, clean RSS
# ---------------------------------------------------------------------------

def _synthetic_tree(n_kernels: int, d: int):
    rng = np.random.default_rng(0)
    return {"layers": {f"l{i}": {"kernel": rng.standard_normal(
        (d, d)).astype(np.float32) * 0.02} for i in range(n_kernels)},
        "final_norm": {"scale": np.ones((d,), np.float32)}}


def _child(mode: str, n_kernels: int, d: int, out_json: str):
    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.artifacts import write_artifact
    from repro.core.quantize_model import quantize_kernel, quantize_tree

    tree = _synthetic_tree(n_kernels, d)
    pcfg = PTQTPConfig(group_size=128, t_max=5)
    # warm the quantizer jit (same shape for every kernel) so the measured
    # phase is throughput, not compilation
    jax.block_until_ready(quantize_kernel(
        jnp.asarray(tree["layers"]["l0"]["kernel"]), pcfg).alpha)

    rss0 = _max_rss_kb()
    t0 = time.perf_counter()
    if mode == "inmem":
        qp, report = quantize_tree(tree, pcfg)
        jax.block_until_ready([l for l in jax.tree.leaves(qp)])
        n_q = report["__total__"]["n_quantized"]
        # what a quantize-at-boot server must hold live: the whole packed
        # tree at once — O(model)
        resident_mb = report["__total__"]["after_bytes"] / 1e6
    else:
        # "stream" = default fsync group commit; "stream_fsync1" = PR-3's
        # per-tensor durability
        commit_every = 1 if mode == "stream_fsync1" else None
        with tempfile.TemporaryDirectory() as td:
            out = write_artifact(
                Path(td) / "art", arch="qwen2-1.5b",
                model_cfg=configs.get_smoke_config("qwen2-1.5b"),
                ptqtp_cfg=pcfg, params=tree, compute_error=False,
                commit_every=commit_every)
            m = json.loads((out / "manifest.json").read_text())
            n_q = m["stats"]["n_quantized"]
            # what the streaming writer holds live: one tensor's buffers at
            # a time — O(largest kernel)
            resident_mb = max(
                sum(b["nbytes"] for b in rec["buffers"].values())
                for rec in m["tensors"].values()) / 1e6
    dt = time.perf_counter() - t0
    payload = {
        "seconds": dt,
        "n_quantized": n_q,
        "weight_mb": n_kernels * d * d * 4 / 1e6,
        "peak_rss_growth_mb": (_max_rss_kb() - rss0) / 1024.0,
        "resident_quantized_mb": resident_mb,
    }
    Path(out_json).write_text(json.dumps(payload))


def _bench_write(rows, log, quick):
    from repro.artifacts.writer import ArtifactWriter

    n_kernels, d = (6, 256) if quick else (16, 1024)
    env = dict(os.environ, JAX_PLATFORMS="cpu")  # host RSS, never the chip
    env["PYTHONPATH"] = f"{ROOT / 'src'}" + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    for mode in ("inmem", "stream", "stream_fsync1"):
        with tempfile.NamedTemporaryFile(suffix=".json") as f:
            subprocess.run(
                [sys.executable, str(Path(__file__).resolve()), "--_child",
                 mode, "--_n", str(n_kernels), "--_d", str(d),
                 "--_out", f.name],
                check=True, env=env, cwd=ROOT)
            r = json.loads(Path(f.name).read_text())
        rows[f"write_{mode}_s"] = r["seconds"]
        rows[f"write_{mode}_mb_per_s"] = r["weight_mb"] / r["seconds"]
        rows[f"write_{mode}_peak_rss_growth_mb"] = r["peak_rss_growth_mb"]
        rows[f"write_{mode}_resident_quantized_mb"] = \
            r["resident_quantized_mb"]
        log(f"bench_artifacts,write_{mode}_s,{r['seconds']:.2f}")
        log(f"bench_artifacts,write_{mode}_peak_rss_growth_mb,"
            f"{r['peak_rss_growth_mb']}")
        log(f"bench_artifacts,write_{mode}_resident_quantized_mb,"
            f"{r['resident_quantized_mb']:.2f}")
    rows["write_weight_mb"] = n_kernels * d * d * 4 / 1e6
    # the structural claim: in-memory holds the whole packed tree (O(model)),
    # streaming holds one tensor (O(largest kernel)); raw RSS deltas ride
    # along but are allocator-noise-dominated at smoke scale
    rows["write_resident_ratio"] = (
        rows["write_inmem_resident_quantized_mb"]
        / max(rows["write_stream_resident_quantized_mb"], 1e-9))
    # fsync group commit: the durability overhead it amortizes, and whether
    # streaming now beats the in-memory walk outright
    rows["write_group_commit_every"] = ArtifactWriter.DEFAULT_COMMIT_EVERY
    rows["write_fsync_batching_speedup"] = (
        rows["write_stream_fsync1_s"] / max(rows["write_stream_s"], 1e-9))
    rows["write_stream_vs_inmem_speedup"] = (
        rows["write_inmem_s"] / max(rows["write_stream_s"], 1e-9))
    log(f"bench_artifacts,write_fsync_batching_speedup,"
        f"{rows['write_fsync_batching_speedup']:.2f}")
    log(f"bench_artifacts,write_stream_vs_inmem_speedup,"
        f"{rows['write_stream_vs_inmem_speedup']:.2f}")


# ---------------------------------------------------------------------------
# boot TTFT: quantize-at-boot vs artifact memmap boot
# ---------------------------------------------------------------------------

def _boot_ttft(params_fn, prompt, max_new):
    from repro import configs
    from repro.serving import (EngineConfig, SamplingParams, ServingEngine)

    cfg = configs.get_smoke_config("qwen2-1.5b")
    t0 = time.perf_counter()
    params = params_fn()
    eng = ServingEngine(params, cfg, EngineConfig(max_slots=4, capacity=128,
                                                  seed=0))
    h = eng.submit(prompt, SamplingParams(max_new_tokens=max_new))
    res = h.result()
    return res.t_first - t0, res.tokens


def _bench_boot(rows, log, quick, tmp_dir):
    import jax

    from repro import configs
    from repro.artifacts import load_artifact, write_artifact
    from repro.core.quantize_model import quantize_tree
    from repro.models import init_params

    cfg = configs.get_smoke_config("qwen2-1.5b")
    pcfg = PTQTPConfig(group_size=32, t_max=5)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompt, max_new = [1, 2, 3, 4], 8 if quick else 16

    # quantize once (untimed): the artifact every subsequent server boots from
    art = Path(tmp_dir) / "boot_artifact"
    write_artifact(art, arch="qwen2-1.5b", model_cfg=cfg, ptqtp_cfg=pcfg,
                   params=params, overwrite=True)
    # warm ambient XLA state with a throwaway FP engine so neither timed
    # path gets the cold-runtime penalty
    _boot_ttft(lambda: params, prompt, 2)

    ttft_q, out_q = _boot_ttft(
        lambda: quantize_tree(params, pcfg)[0], prompt, max_new)
    ttft_a, out_a = _boot_ttft(
        lambda: load_artifact(art)[0], prompt, max_new)

    rows["boot_quantize_ttft_s"] = ttft_q
    rows["boot_artifact_ttft_s"] = ttft_a
    rows["boot_outputs_identical"] = out_q == out_a
    rows["boot_ttft_speedup"] = ttft_q / ttft_a
    rows["artifact_boot_faster"] = ttft_a < ttft_q
    log(f"bench_artifacts,boot_quantize_ttft_s,{ttft_q:.2f}")
    log(f"bench_artifacts,boot_artifact_ttft_s,{ttft_a:.2f}")
    log(f"bench_artifacts,boot_ttft_speedup,{ttft_q / ttft_a:.2f}")
    return art


def _bench_disk(rows, log, art):
    from repro.artifacts import read_manifest

    m = read_manifest(art)
    stats = m["stats"]
    g = m["ptqtp_config"]["group_size"]
    rows["disk_bytes_per_weight"] = stats["bytes_per_weight"]
    # fp32 scales keep artifact boot bit-identical to in-process quantize;
    # Eq. 13's fp16-scale figure at the same G, and the paper's G=128
    # constant, sit alongside for the gap analysis
    rows["disk_bytes_per_weight_fp16_scales"] = 0.5 + 2 * 2 / g
    rows["disk_paper_theoretical_g128"] = 0.53125
    rows["disk_group_size"] = g
    rows["disk_total_mb"] = stats["total_bytes"] / 1e6
    rows["disk_vs_fp16_compression"] = (stats["source_fp16_bytes"]
                                        / stats["quantized_bytes"])
    for k in ("disk_bytes_per_weight", "disk_bytes_per_weight_fp16_scales",
              "disk_vs_fp16_compression"):
        log(f"bench_artifacts,{k},{rows[k]:.4f}")


def run(log=print, quick=False):
    rows = {}
    with tempfile.TemporaryDirectory() as td:
        _bench_write(rows, log, quick)
        art = _bench_boot(rows, log, quick, td)
        _bench_disk(rows, log, art)
        rows["headline_boot_ttft_speedup"] = rows["boot_ttft_speedup"]
        log(f"bench_artifacts,headline_boot_ttft_speedup,"
            f"{rows['headline_boot_ttft_speedup']:.2f}")
        save_result("BENCH_artifacts", rows)
        (ROOT / "BENCH_artifacts.json").write_text(
            json.dumps(rows, indent=1, default=float))
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke sizes (seconds, not minutes)")
    ap.add_argument("--_child", choices=("inmem", "stream", "stream_fsync1"),
                    default=None, help=argparse.SUPPRESS)
    ap.add_argument("--_n", type=int, default=8, help=argparse.SUPPRESS)
    ap.add_argument("--_d", type=int, default=1024, help=argparse.SUPPRESS)
    ap.add_argument("--_out", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args._child:
        _child(args._child, args._n, args._d, args._out)
    else:
        run(quick=args.quick)
