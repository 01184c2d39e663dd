"""Dense GQA decoder (model_type ``qwen2``): what the harness counts for it.

Every layer is attention then a SwiGLU MLP, with biases on q, k and v:
seven ternary matrices, two norm scales and the three biases, and one call
of the chunk-attention kernel over the KV ring. No state beyond the ring.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Optional

from harness import work

SHRINK = dict(hidden_size=128, intermediate_size=256, num_attention_heads=2,
              num_key_value_heads=1, num_hidden_layers=2, vocab_size=512)

KERNELS = {"chunk_attention": ("chunk_attention",)}

# rows, kv heads, group x chunk, head size
_ATTENTION_RESULT = re.compile(r"^f32\[\d+,\d+,\d+,\d+\]$")


def unnamed_kernel(result: str, operands: int) -> Optional[str]:
    """The chunk-attention ``pallas_call`` has no name of its own in a
    trace (it shows as the call around it, ``%closed_call.13``): a TPU
    custom call with a rank-4 f32 result and the kernel's 11 operands (12
    when paged)."""
    if _ATTENTION_RESULT.match(result) and operands in (11, 12):
        return "chunk_attention"
    return None


def model_config(c: Dict[str, Any]) -> Dict[str, Any]:
    return dict(
        family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c["hidden_size"] // c["num_attention_heads"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        qkv_bias=True, rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]), block_pattern=("attn+mlp",),
        mlp_type="swiglu")


def layer(model: work.Model, i: int) -> work.Layer:
    c = model.config
    d, ff = c["hidden_size"], c["intermediate_size"]
    heads, kv_heads = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // heads
    hq, hkv = heads * hd, kv_heads * hd
    return work.Layer(
        matrices=[(d, hq), (d, hkv), (d, hkv), (hq, d),
                  (d, ff), (d, ff), (ff, d)],
        dense_param_bytes=work.BF16 * (hq + 2 * hkv + 2 * d),
        kernels={"chunk_attention": lambda rows: work.attention(
            model, rows, heads, kv_heads, hd)})
