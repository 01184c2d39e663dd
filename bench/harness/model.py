"""A configuration file (published keys, as run) to the program's configs.

The configuration files keep the published ``config.json`` vocabulary
(``hidden_size``, ``num_hidden_layers``, ...); this module maps each
``model_type`` onto the program's ``ModelConfig`` and the file's
``engine`` block onto ``EngineConfig``. A new model of a type listed here
is a new data file only.
"""

from __future__ import annotations

from typing import Any, Dict


def _dense_gqa(c: Dict[str, Any], dtype: str) -> Dict[str, Any]:
    return dict(
        family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c["hidden_size"] // c["num_attention_heads"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        qkv_bias=True, rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]), block_pattern=("attn+mlp",),
        mlp_type="swiglu")


def _rwkv6(c: Dict[str, Any], dtype: str) -> Dict[str, Any]:
    d, hd = c["hidden_size"], c["head_size"]
    return dict(
        family="ssm", n_layers=c["num_hidden_layers"], d_model=d,
        n_heads=d // hd, n_kv_heads=d // hd, d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], block_pattern=("rwkv",),
        rwkv_head_dim=hd, norm_eps=float(c["layer_norm_epsilon"]))


_BUILDERS = {"qwen2": _dense_gqa, "rwkv6": _rwkv6}


def model_config(c: Dict[str, Any]):
    """The program's ``ModelConfig`` for configuration file ``c``."""
    from repro.configs.base import ModelConfig

    if c["model_type"] not in _BUILDERS:
        raise KeyError(f"no mapping for model_type {c['model_type']!r}")
    dtype = c["torch_dtype"]
    q = c["quantization"]
    return ModelConfig(
        name=c["name"], param_dtype=dtype, activation_dtype=dtype,
        kv_cache_dtype=q.get("kv_cache_dtype", "bfloat16"), remat="none",
        **_BUILDERS[c["model_type"]](c, dtype))


def engine_config(c: Dict[str, Any]):
    from repro.serving import EngineConfig

    return EngineConfig(**c["engine"])


def fairness(c: Dict[str, Any]):
    """The driver's fair admission as the file's ``frontend`` block sets
    it (``FairScheduler`` keywords, as ``serve.py --tenant-quantum`` does);
    without the block, the scheduler's defaults."""
    from repro.serving.frontend import FairScheduler

    return FairScheduler(**c.get("frontend", {}))
