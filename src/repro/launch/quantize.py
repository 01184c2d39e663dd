"""Offline quantization CLI: FP weights → trit-plane artifact, streamed.

``python -m repro.launch.quantize --arch qwen2-1.5b --out artifacts/qwen``

The production half of "quantize once, serve many": walk the params tree one
kernel at a time (peak incremental host memory O(largest kernel)), append
packed trit-planes to the artifact shards, and commit each tensor atomically
— an interrupted run resumes from the staging manifest, skipping everything
already committed. Serve from the result with
``python -m repro.launch.serve --artifact <out>`` (no FP weights, no
re-quantization at boot).

Weight sources: ``--seed`` initialization (smoke/demo) or
``--from-checkpoint DIR`` (a ``runtime/checkpoint.py`` training checkpoint,
streamed lazily out of the npz so the FP tree is never fully materialized).
"""

from __future__ import annotations

import argparse
import time

from repro import configs
from repro.artifacts import (iter_checkpoint_leaves, verify_artifact,
                             write_artifact)
from repro.core.ptqtp import PTQTPConfig
from repro.runtime.compile_cache import enable_compile_cache


def _progress_printer(every: int = 1):
    state = {"quantized": 0, "skipped": 0, "fp": 0}

    def progress(ev):
        state[{"quantize": "quantized", "skip": "skipped"}.get(
            ev["action"], "fp")] += 1
        if ev["action"] == "quantize":
            err = (ev.get("error") or {}).get("rel_fro_error")
            err_s = f" err={err:.4f}" if err is not None else ""
            if state["quantized"] % every == 0:
                print(f"[quantize] #{ev['index']:>3} {ev['path']} "
                      f"shape={ev['shape']}{err_s} "
                      f"({ev['elapsed']:.1f}s)", flush=True)
        elif ev["action"] == "skip" and state["skipped"] == 1:
            print("[quantize] resuming: skipping tensors already committed "
                  "in the staging manifest", flush=True)

    progress.state = state
    return progress


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=configs.ARCH_IDS, default="qwen2-1.5b")
    ap.add_argument("--out", required=True, help="artifact directory to write")
    ap.add_argument("--config", choices=("smoke", "full"), default="smoke",
                    help="model size: smoke (default) or the paper-scale "
                         "config (needs the weights to exist!)")
    ap.add_argument("--from-checkpoint", default=None, metavar="DIR",
                    help="stream FP weights out of a training checkpoint "
                         "instead of --seed initialization")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--group-size", type=int, default=0,
                    help="PTQTP group size G (0 → min(128, d_model))")
    ap.add_argument("--t-max", type=int, default=20)
    ap.add_argument("--commit-every", type=int, default=None, metavar="N",
                    help="fsync group-commit size: make tensors durable "
                         "every N commits (1 = per-tensor, the slowest but "
                         "finest-grained resume; default 8)")
    ap.add_argument("--no-resume", action="store_true",
                    help="ignore any staging manifest and restart")
    ap.add_argument("--overwrite", action="store_true",
                    help="replace an existing artifact at --out")
    ap.add_argument("--no-error-stats", action="store_true",
                    help="skip the per-kernel approximation-error pass")
    ap.add_argument("--verify", action="store_true",
                    help="re-checksum the finished artifact")
    args = ap.parse_args(argv)

    cfg = (configs.get_smoke_config(args.arch) if args.config == "smoke"
           else configs.get_config(args.arch))
    gs = args.group_size or min(128, cfg.d_model)
    pcfg = PTQTPConfig(group_size=gs, t_max=args.t_max)

    if args.from_checkpoint:
        params = iter_checkpoint_leaves(args.from_checkpoint)
        src = f"checkpoint {args.from_checkpoint}"
    else:
        import jax

        from repro.models import init_params

        params = init_params(cfg, jax.random.PRNGKey(args.seed))
        src = f"seed {args.seed}"

    print(f"[quantize] {args.arch} ({args.config}) from {src} "
          f"→ {args.out}  G={gs} t_max={args.t_max}", flush=True)
    progress = _progress_printer()
    t0 = time.time()
    out = write_artifact(
        args.out, arch=args.arch, model_cfg=cfg, ptqtp_cfg=pcfg,
        params=params, compute_error=not args.no_error_stats,
        progress=progress, resume=not args.no_resume,
        overwrite=args.overwrite, commit_every=args.commit_every)
    dt = time.time() - t0

    from repro.artifacts import read_manifest

    stats = read_manifest(out)["stats"]
    st = progress.state
    print(f"[quantize] done in {dt:.1f}s: {st['quantized']} kernels "
          f"quantized, {st['fp']} FP leaves, {st['skipped']} resumed; "
          f"{stats['total_bytes'] / 1e6:.2f} MB on disk "
          f"({stats.get('bytes_per_weight', float('nan')):.4f} B/weight, "
          f"{stats['source_fp16_bytes'] / max(stats['quantized_bytes'], 1):.2f}x "
          f"vs fp16)", flush=True)
    if args.verify:
        verify_artifact(out)
        print("[quantize] verify: all checksums OK", flush=True)
    return out


if __name__ == "__main__":
    main()
