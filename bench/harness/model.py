"""A configuration file (published keys, as run) to the program's configs.

The configuration files keep the published ``config.json`` vocabulary
(``hidden_size``, ``num_hidden_layers``, ...); the architecture module of
the file's ``model_type`` (``bench/configs/arch_<model_type>.py``, see
``spec.load_arch``) maps it onto the program's ``ModelConfig``, and this
module adds what every architecture shares: dtypes, the KV cache's dtype,
the file's ``engine`` block as ``EngineConfig`` and its ``frontend`` block
as the driver's fair admission.
"""

from __future__ import annotations

from typing import Any, Dict


def model_config(c: Dict[str, Any], arch):
    """The program's ``ModelConfig`` for configuration file ``c`` of the
    architecture module ``arch``."""
    from repro.configs.base import ModelConfig

    dtype = c["torch_dtype"]
    q = c["quantization"]
    return ModelConfig(
        name=c["name"], param_dtype=dtype, activation_dtype=dtype,
        kv_cache_dtype=q.get("kv_cache_dtype", "bfloat16"), remat="none",
        **arch.model_config(c))


def engine_config(c: Dict[str, Any]):
    from repro.serving import EngineConfig

    return EngineConfig(**c["engine"])


def fairness(c: Dict[str, Any]):
    """The driver's fair admission as the file's ``frontend`` block sets
    it (``FairScheduler`` keywords, as ``serve.py --tenant-quantum`` does);
    without the block, the scheduler's defaults."""
    from repro.serving.frontend import FairScheduler

    return FairScheduler(**c.get("frontend", {}))
