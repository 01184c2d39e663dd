"""A benchmark tree at test size: the real BENCHMARK.json, configuration
files cut to a few widths by their architecture module's ``SHRINK``, and
short traffic, in a temporary directory."""

from __future__ import annotations

import json
import math
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import spec  # noqa: E402

LIMIT = 1e-3
TRAFFIC = {
    "chat": {"kind": "open_loop",
             "arrivals": {"process": "poisson", "rate_per_s": 4.0},
             "warm_s": 0.5, "order": "fixed",
             "prompt_tokens": {"dist": "lognormal", "median": 12,
                               "sigma": 0.8, "min": 4, "max": 40},
             "output_tokens": {"dist": "lognormal", "median": 6,
                               "sigma": 0.8, "min": 4, "max": 16}},
    "longgen": {"kind": "backlog", "backlog": 2, "requests": 4096,
                "prompt_tokens": {"dist": "uniform", "min": 4, "max": 16},
                "output_tokens": {"dist": "uniform", "min": 40, "max": 120}},
}


def top() -> dict:
    """``BENCHMARK.json`` as committed."""
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def config(name: str, bench: Path = BENCH) -> dict:
    """The configuration file of config ``name`` under ``bench``."""
    entry = {c["name"]: c for c in top()["configs"]}[name]
    return json.loads((bench.parent / entry["file"]).read_text())


def arch(c: dict, bench: Path = BENCH):
    """The architecture module of configuration file ``c``."""
    return spec.load_arch(bench, c["model_type"])


def tiny_tree(dest: Path) -> Path:
    """Write the tree under ``dest``; returns its bench directory."""
    bench = dest / "bench"
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    for entry in top()["configs"]:
        c = json.loads((ROOT / entry["file"]).read_text())
        c.update(arch(c).SHRINK, torch_dtype="float32")
        c["engine"] = {"max_slots": 4, "capacity": 256, "prefill_chunk": 8,
                       "decode_chunk": 4, "kv_layout": "ring"}
        # the program reads 0 at float32 on the CPU: any wrong token or
        # state reads far above the tiny limit of each compared statistic
        c["correct"] = {k: (LIMIT if k.endswith("_logit_gap") else v)
                        for k, v in c["correct"].items()}
        c["correct"]["tokens_to_compare"] = 16
        (dest / entry["file"]).write_text(json.dumps(c))
        for f in (c["reference"], f"arch_{c['model_type']}.py"):
            shutil.copy(BENCH / "configs" / f, bench / "configs" / f)
    for name, t in TRAFFIC.items():
        (bench / "traffic" / f"{name}.json").write_text(json.dumps(t))
    return bench


def quantized_matrices(c: dict, arch):
    """The (d_in, d_out) of every matrix that the program's own quantizer
    picks: per layer, sorted (a stacked leaf counts once for each layer of
    the scan, a stack of experts once for each expert), and the others
    (the output head)."""
    from harness import model, weights

    mcfg = model.model_config(c, arch)
    first, period, periods = (len(mcfg.prefix_pattern), mcfg.period,
                              mcfg.n_periods)
    layers = [[] for _ in range(mcfg.n_layers)]
    rest = []
    for path, sds, ternary in weights.leaves(
            mcfg, c["quantization"]["group_size"]):
        if not ternary:
            continue
        part, place = path.strip("/").split("/")[:2]
        shape = tuple(sds.shape)
        if part == "blocks":
            at = [first + j * period + int(place[1:]) for j in range(periods)]
            shape = shape[1:]
        elif part == "prefix":
            at = [int(place[1:])]
        elif part == "suffix":
            at = [first + periods * period + int(place[1:])]
        else:
            rest.append(shape)
            continue
        *experts, d_in, d_out = shape
        for i in at:
            layers[i] += [(d_in, d_out)] * math.prod(experts)
    return [sorted(m) for m in layers], rest
