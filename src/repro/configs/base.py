"""Model/config schema shared by all assigned architectures.

A model is a *block pattern*: an optional prefix, a repeating period (scanned
with `lax.scan` so compile time is O(1) in depth), and an automatic remainder.
Block kinds compose a token mixer and a channel mixer:

  "attn+mlp"   full-causal GQA + FFN            (llama/qwen/musicgen/phi)
  "local+mlp"  sliding-window GQA + FFN         (gemma3 local, recurrentgemma)
  "attn+moe"   full-causal GQA + MoE FFN        (grok, deepseek)
  "mla+mlp"    latent attention (MLA) + FFN      (deepseek-v2 dense layers)
  "mla+moe"    latent attention (MLA) + MoE FFN  (deepseek-v2)
  "rwkv"       RWKV6 time-mix + channel-mix
  "rglru+mlp"  RG-LRU recurrent block + FFN
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from repro.models.common import YarnRope
from repro.models.moe import MoEConfig

SHAPES = {
    "train_4k": {"seq_len": 4096, "global_batch": 256, "kind": "train"},
    "prefill_32k": {"seq_len": 32768, "global_batch": 32, "kind": "prefill"},
    "decode_32k": {"seq_len": 32768, "global_batch": 128, "kind": "decode"},
    "long_500k": {"seq_len": 524288, "global_batch": 1, "kind": "decode"},
}


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None
    qkv_bias: bool = False
    mlp_type: str = "swiglu"
    block_pattern: Tuple[str, ...] = ("attn+mlp",)
    prefix_pattern: Tuple[str, ...] = ()
    window: Optional[int] = None       # sliding-window size for "local" blocks
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    embed_inputs: bool = True          # False: stub frontend feeds embeddings
    moe: Optional[MoEConfig] = None
    # latent attention ("mla+*" blocks, DeepSeek-V2): a kv_lora_rank-wide
    # latent plus one shared rope key per token is all the cache holds
    kv_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_yarn: Optional[YarnRope] = None
    rope_interleave: bool = False      # rotation pairs (2i, 2i+1)
    # rwkv / rglru
    rwkv_head_dim: int = 64
    rglru_width: Optional[int] = None
    rglru_blocks: Optional[int] = None  # block-diag gate blocks (≈ n_heads)
    conv_width: int = 4
    # infra
    scan_layers: bool = True
    remat: str = "full"                # none | full
    kv_cache_dtype: str = "bfloat16"   # bfloat16 | int8 (§Perf iteration 5)
    # serving chunk/decode attention backend over the ring cache:
    # auto | pallas | stream | materialized (repro.kernels.chunk_attention)
    attn_backend: str = "auto"
    param_dtype: str = "bfloat16"
    activation_dtype: str = "bfloat16"
    optimizer_dtype: str = "float32"   # adam moment dtype (bf16 for 405B)
    microbatches: int = 1              # gradient-accumulation chunks
    q_chunk: int = 1024                # attention query-chunk size
    # dry-run bookkeeping
    supports_long_context: bool = False  # sub-quadratic mixers only

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        assert self.n_heads % self.n_kv_heads == 0

    # ---- pattern layout -------------------------------------------------
    @property
    def period(self) -> int:
        return len(self.block_pattern)

    @property
    def n_periods(self) -> int:
        return (self.n_layers - len(self.prefix_pattern)) // self.period

    @property
    def remainder_pattern(self) -> Tuple[str, ...]:
        rem = (self.n_layers - len(self.prefix_pattern)) % self.period
        return self.block_pattern[:rem]

    @property
    def layer_kinds(self):
        """Flat list of all n_layers block kinds, in order."""
        full = list(self.prefix_pattern)
        full += list(self.block_pattern) * self.n_periods
        full += list(self.remainder_pattern)
        assert len(full) == self.n_layers, (len(full), self.n_layers)
        return full

    def scaled(self, **kw) -> "ModelConfig":
        """Reduced-config derivation for smoke tests."""
        return dataclasses.replace(self, **kw)

    # ---- parameter accounting (roofline: MODEL_FLOPS = 6·N·D) -----------
    def param_counts(self):
        """(total_params, active_params) analytic counts."""
        d, hd = self.d_model, self.head_dim
        attn = d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd \
            + self.n_heads * hd * d
        if self.kv_lora_rank:
            r, rope = self.kv_lora_rank, self.qk_rope_head_dim
            nope, v, h = self.qk_nope_head_dim, self.v_head_dim, self.n_heads
            mla = (d * h * (nope + rope) + d * (r + rope)
                   + r * h * (nope + v) + h * v * d)
        mlps = {"swiglu": 3, "geglu": 3, "gelu": 2}[self.mlp_type]
        mlp = mlps * d * self.d_ff
        rwkv = 5 * d * d + d * self.d_ff * 2 + d * d  # time (5 proj) + channel
        rg = self.rglru_width or d
        nb = self.rglru_blocks or 1
        rglru = 2 * d * rg + rg * d + 2 * nb * (rg // nb) ** 2 + 4 * rg

        total = active = 0
        for kind in self.layer_kinds:
            if kind == "rwkv":
                total += rwkv
                active += rwkv
            elif kind.startswith("rglru"):
                total += rglru + mlp
                active += rglru + mlp
            else:
                a = mla if kind.startswith("mla") else attn
                total += a
                active += a
                if kind.endswith("moe"):
                    m = self.moe
                    e_p = 3 * d * m.d_expert
                    total += m.n_experts * e_p + d * m.n_experts
                    active += m.top_k * e_p + d * m.n_experts
                    if m.n_shared:
                        total += 3 * d * m.n_shared * m.d_expert
                        active += 3 * d * m.n_shared * m.d_expert
                else:
                    total += mlp
                    active += mlp
        emb = self.vocab_size * d
        total += emb * 2  # embed + lm_head
        active += emb * 2
        return total, active
