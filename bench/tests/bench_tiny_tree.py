"""A benchmark tree at test size: the real BENCHMARK.json, configuration
files cut to a few widths, and short traffic, in a temporary directory."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SHRINK = {
    "qwen2-1.5b": dict(hidden_size=128, intermediate_size=256,
                       num_attention_heads=2, num_key_value_heads=1,
                       num_hidden_layers=2, vocab_size=512),
    "rwkv6-3b": dict(hidden_size=128, attention_hidden_size=128,
                     intermediate_size=256, num_hidden_layers=2,
                     vocab_size=512),
}
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


def tiny_tree(dest: Path) -> Path:
    """Write the tree under ``dest``; returns its bench directory."""
    bench = dest / "bench"
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    top = json.loads((ROOT / "BENCHMARK.json").read_text())
    for entry in top["configs"]:
        c = json.loads((ROOT / entry["file"]).read_text())
        c.update(SHRINK[entry["name"]], torch_dtype="float32")
        c["engine"] = {"max_slots": 4, "capacity": 256, "prefill_chunk": 8,
                       "decode_chunk": 4, "kv_layout": "ring"}
        # the program reads 0 at float32 on the CPU: any wrong token or
        # state reads far above the tiny limit of each compared statistic
        c["correct"] = {k: (LIMIT if k.endswith("_logit_gap") else v)
                        for k, v in c["correct"].items()}
        c["correct"]["tokens_to_compare"] = 16
        (dest / entry["file"]).write_text(json.dumps(c))
        shutil.copy(BENCH / "configs" / c["reference"],
                    bench / "configs" / c["reference"])
    for name, t in TRAFFIC.items():
        (bench / "traffic" / f"{name}.json").write_text(json.dumps(t))
    return bench
