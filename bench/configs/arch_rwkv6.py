"""RWKV-6 "Finch" (model_type ``rwkv6``): what the harness counts for it.

Every layer is a time mix (token shift with data-dependent LoRA mixes, a
decay LoRA, the WKV recurrence over an f32 state of heads × hd × hd) and
a channel mix: eight ternary matrices, the LoRAs and vectors at bf16, no
kernel but the ternary matmul. The recurrent state of each live row is
read and written once per call.
"""

from __future__ import annotations

from typing import Any, Dict

from harness import work
from harness.weights import ints, signed

SHRINK = dict(hidden_size=128, attention_hidden_size=128,
              intermediate_size=256, num_hidden_layers=2, vocab_size=512)

MIX_LORA, DECAY_LORA = 160, 64      # 5 mixes x rank 32; the decay's rank

# name -> rule(key, shape, path) giving f32 values exact in bf16
LEAF_RULES = {
    "mu_x": lambda k, s, p: ints(k, s, 0, 255) * 2.0 ** -8,
    "mu": lambda k, s, p: ints(k, s, 0, 255) * 2.0 ** -8,
    "mu_k": lambda k, s, p: ints(k, s, 0, 255) * 2.0 ** -8,
    "mu_r": lambda k, s, p: ints(k, s, 0, 255) * 2.0 ** -8,
    "decay_base": lambda k, s, p: -6.0 + ints(k, s, 0, 40) * 2.0 ** -3,
    "u": lambda k, s, p: signed(k, s, -10),
    "mix_lora_a": lambda k, s, p: signed(k, s, -13),
    "mix_lora_b": lambda k, s, p: signed(k, s, -13),
    "decay_lora_a": lambda k, s, p: signed(k, s, -13),
    "decay_lora_b": lambda k, s, p: signed(k, s, -13),
}


def model_config(c: Dict[str, Any]) -> Dict[str, Any]:
    d, hd = c["hidden_size"], c["head_size"]
    return dict(
        family="ssm", n_layers=c["num_hidden_layers"], d_model=d,
        n_heads=d // hd, n_kv_heads=d // hd, d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], block_pattern=("rwkv",),
        rwkv_head_dim=hd, norm_eps=float(c["layer_norm_epsilon"]))


def layer(model: work.Model, i: int) -> work.Layer:
    c = model.config
    d, ff, hd = c["hidden_size"], c["intermediate_size"], c["head_size"]
    heads = d // hd
    lora = d * MIX_LORA + MIX_LORA * d + d * DECAY_LORA + DECAY_LORA * d
    vecs = d + 5 * d + d + heads * hd + d + 2 * d + 2 * d
    return work.Layer(
        matrices=[(d, d)] * 5 + [(d, ff), (ff, d), (d, d)],
        dense_param_bytes=work.BF16 * (lora + vecs),
        # token-shift and decay LoRAs, WKV update
        dense_flops_per_token=2.0 * lora + 7.0 * heads * hd * hd,
        state_bytes_per_row=heads * hd * hd * 4 + 2 * d * work.BF16)
