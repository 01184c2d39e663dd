"""DeepSeek-V2 (model_type ``deepseek_v2``): what the harness counts for it.

Every layer is latent attention (MLA, no query LoRA: ``wq``, ``wkv_a``,
``wkv_b``, ``wo`` ternary, the latent's RMSNorm scale) and one call of the
latent-attention kernel over the latent ring. The first
``first_k_dense_replace`` layers then run a dense SwiGLU; every other
layer a router (f32), the routed experts in the grouped ``expert_matmul``
kernel, and the shared experts as one SwiGLU of ``n_shared_experts`` ×
``moe_intermediate_size`` through the ternary matmul. No state beyond the
latent ring.

Counted as the served tokens need it:

* ``kv_b_proj`` is one (kv_lora_rank, heads × (nope + v)) matrix per
  token, as if applied un-absorbed; the absorbed products the program runs
  (q_nope · W_UKᵀ, latent output · W_UV) take the same FLOPs.
* ``expert_matmul``: the routed experts' matrices (listed among the
  ternary matrices at share 0: no row of theirs reaches the ternary
  matmul) take 2 · R · top_k · 3 · d · d_expert FLOPs over the R
  live tokens of a call, and the planes of the experts those tokens hit,
  counted as the number uniform routing hits on average, E · (1 − (1 −
  top_k / E)^R): an assumption (the program reports its routed rows, not
  which experts they went to).
* ``latent_attention``: 2 · heads · (latent + rope + latent) FLOPs per
  visible key (scores over the cached vector, values from its latent),
  and the ring's (latent + rope) × 2 bytes per live position.
* A dense matrix whose contraction is not a multiple of the group (layer
  0's down projection, d_in 10,944) stays bf16 in the program: its bytes
  and FLOPs count under ``other``.
"""

from __future__ import annotations

from typing import Any, Dict

from harness import work
from harness.weights import signed

SHRINK = dict(hidden_size=128, intermediate_size=256,
              moe_intermediate_size=128, n_routed_experts=8,
              num_experts_per_tok=2, n_shared_experts=1,
              num_attention_heads=2, num_key_value_heads=2,
              kv_lora_rank=128, qk_nope_head_dim=32, qk_rope_head_dim=32,
              v_head_dim=64, num_hidden_layers=3, vocab_size=512)

KERNELS = {"latent_attention": ("latent_attention",),
           "expert_matmul": ("expert_matmul",)}


def _kernel_rule(k, s, path):
    """The two non-ternary ``kernel`` leaves: the router (f32; logits of
    a standard-normal input spread about ±0.8 at d 2048) and a dense
    matrix whose contraction the group does not divide (bf16, about the
    1/sqrt(d_in) scale of the program's initializer at d_in 10,944)."""
    return signed(k, s, -12 if "/router/" in path else -13)


LEAF_RULES = {"kernel": _kernel_rule}

# what the program implements of the published config, checked, not assumed
_FIXED = {"q_lora_rank": None, "topk_method": "greedy",
          "scoring_func": "softmax", "n_group": 1, "topk_group": 1,
          "moe_layer_freq": 1, "attention_bias": False, "hidden_act": "silu",
          "routed_scaling_factor": 1}


def model_config(c: Dict[str, Any]) -> Dict[str, Any]:
    from repro.models.common import YarnRope
    from repro.models.moe import MoEConfig

    odd = {k: c.get(k) for k, v in _FIXED.items() if c.get(k) != v}
    if odd:
        raise ValueError(f"deepseek_v2 settings the program lacks: {odd}")
    rs = c["rope_scaling"]
    if rs.get("type") != "yarn":
        raise ValueError(f"rope_scaling {rs.get('type')!r} is not yarn")
    return dict(
        family="moe", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        prefix_pattern=("mla+mlp",) * c["first_k_dense_replace"],
        block_pattern=("mla+moe",), mlp_type="swiglu",
        moe=MoEConfig(n_experts=c["n_routed_experts"],
                      top_k=c["num_experts_per_tok"],
                      d_expert=c["moe_intermediate_size"],
                      n_shared=c["n_shared_experts"], capacity_factor=-1.0,
                      norm_topk_prob=bool(c["norm_topk_prob"])),
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        rope_yarn=YarnRope(
            factor=float(rs["factor"]),
            original_max_position=int(rs["original_max_position_embeddings"]),
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])),
        rope_interleave=True)


def latent(model: work.Model, rows, heads: int, rank: int, rope: int):
    """(operations, bytes) of one layer's latent-attention call: each
    query scores every live cached vector up to itself (rank + rope lanes)
    and sums their latents (rank lanes)."""
    flops = nbytes = 0.0
    width, cap = rank + rope, model.capacity
    for r in rows:
        if r.n <= 0:
            continue
        keys = sum(min(p + 1, cap) for p in range(r.start, r.start + r.n))
        flops += 2.0 * heads * (width + rank) * keys
        nbytes += min(r.start, cap) * width * work.BF16      # the ring before
        nbytes += r.n * width * work.BF16                    # chunk latents
        nbytes += r.n * heads * (width + rank) * work.BF16   # q in, out
    return flops, nbytes


def experts(model: work.Model, rows):
    """(operations, bytes) of one layer's routed experts over a step's
    live rows: every token's top-k experts, the planes of the experts
    uniform routing hits on average."""
    c = model.config
    d, fe = c["hidden_size"], c["moe_intermediate_size"]
    e, k = c["n_routed_experts"], c["num_experts_per_tok"]
    m = sum(r.n for r in rows)
    if m <= 0:
        return 0.0, 0.0
    hit = e * (1.0 - (1.0 - k / e) ** m)
    planes = hit * (2 * model.ternary_bytes(d, fe) + model.ternary_bytes(fe, d))
    acts = work.BF16 * m * k * 3 * (d + fe)
    return 2.0 * m * k * 3 * d * fe, planes + acts


def layer(model: work.Model, i: int) -> work.Layer:
    c = model.config
    d, heads = c["hidden_size"], c["num_attention_heads"]
    rank, rope = c["kv_lora_rank"], c["qk_rope_head_dim"]
    nope, v = c["qk_nope_head_dim"], c["v_head_dim"]
    mats = [(d, heads * (nope + rope)), (d, rank + rope),
            (rank, heads * (nope + v)), (heads * v, d)]
    dense_bytes = work.BF16 * (2 * d + rank)                  # three norms
    dense_flops = 0.0
    kernels = {"latent_attention": lambda rows: latent(model, rows, heads,
                                                       rank, rope)}
    if i < c["first_k_dense_replace"]:
        ff = c["intermediate_size"]
        mats += [(d, ff), (d, ff)]
        if ff % model.group == 0:
            mats.append((ff, d))
        else:                                   # stays bf16 in the program
            dense_bytes += work.BF16 * ff * d
            dense_flops += 2.0 * ff * d
    else:
        fe = c["moe_intermediate_size"]
        sh = c["n_shared_experts"] * fe
        e = c["n_routed_experts"]
        mats += [(d, sh), (d, sh), (sh, d)]
        # the routed experts are ternary matrices of the model, but none of
        # their rows reach the ternary matmul: share 0 there, and their
        # work is expert_matmul's
        mats += [(d, fe, 0.0), (d, fe, 0.0), (fe, d, 0.0)] * e
        dense_bytes += 4 * d * e                              # f32 router
        dense_flops += 2.0 * d * e
        kernels["expert_matmul"] = lambda rows: experts(model, rows)
    return work.Layer(matrices=mats, dense_param_bytes=dense_bytes,
                      dense_flops_per_token=dense_flops, kernels=kernels)
