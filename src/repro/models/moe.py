"""Mixture-of-Experts FFN: top-k routing, capacity-bounded sort-based dispatch,
optional shared experts (DeepSeek-MoE style), expert-parallel friendly layout.

Training and evaluation (``moe_forward``) use the capacity-bounded dispatch
below. Serving (``moe_serve``) routes without capacity: every valid
token's k assignments are computed, sorted by expert into row tiles of
the grouped ``expert_matmul`` kernel, so a token's output depends only on
that token, and padding rows are routed nowhere.

Dispatch is the sort/scatter formulation (no (T, E, C) one-hot tensors):
  1. router top-k per token, probabilities renormalized over the k winners,
  2. (token, expert) assignments sorted by expert id,
  3. rank-within-expert via counts/segment offsets,
  4. scatter into dense (E, C, D) buffers (capacity-dropped tokens masked),
  5. per-expert FFN as one stacked einsum over the E axis — shardable over
     the `model` mesh axis when E % tp == 0 (expert parallelism), else the
     FFN hidden dim shards (tensor parallelism),
  6. gather + combine back to (T, D).

Capacity C = ceil(T·k/E · capacity_factor) bounds compute and makes the
FLOP count match the active-parameter roofline (6·N_active·D).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models.common import dense, dense_init
from repro.models.mlp import mlp_forward, mlp_init


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_expert: int                 # per-expert FFN hidden size
    n_shared: int = 0             # always-on shared experts (deepseek)
    capacity_factor: float = 1.25
    router_dtype: str = "float32"
    norm_topk_prob: bool = True   # renormalize the k winners' weights


def moe_init(key, d_model: int, moe: MoEConfig, mlp_type: str, dtype) -> Dict[str, Any]:
    ks = jax.random.split(key, 5)
    e, fe = moe.n_experts, moe.d_expert
    std = 1.0 / jnp.sqrt(d_model).astype(jnp.float32)
    params: Dict[str, Any] = {
        "router": dense_init(ks[0], d_model, e, dtype=jnp.float32),
        "experts": {
            "wi": {"kernel": (jax.random.normal(ks[1], (e, d_model, fe)) * std).astype(dtype)},
            "wg": {"kernel": (jax.random.normal(ks[2], (e, d_model, fe)) * std).astype(dtype)},
            "wo": {"kernel": (jax.random.normal(ks[3], (e, fe, d_model))
                              * (1.0 / jnp.sqrt(fe))).astype(dtype)},
        },
    }
    if moe.n_shared:
        params["shared"] = mlp_init(ks[4], d_model, moe.n_shared * fe, mlp_type,
                                    dtype)
    return params


def _expert_ffn(experts: Dict[str, Any], xe: jax.Array) -> jax.Array:
    """xe: (E, C, D) -> (E, C, D) via per-expert SwiGLU."""
    from repro.core.quantize_model import QuantizedKernel
    from repro.kernels.ternary_matmul.ops import ternary_matmul
    from repro.models.common import matmul_backend

    def mm(p, x, eq):
        k = p["kernel"]
        if isinstance(k, QuantizedKernel):
            def one(xi, t1p, t2p, al):
                return ternary_matmul(xi, t1p, t2p, al, group_size=k.group_size,
                                      backend=matmul_backend(), out_dtype=xi.dtype)
            return jax.vmap(one)(x, k.t1p, k.t2p, k.alpha)
        return jnp.einsum(eq, x, k.astype(x.dtype))

    h = jax.nn.silu(mm(experts["wg"], xe, "ecd,edf->ecf")) * mm(
        experts["wi"], xe, "ecd,edf->ecf")
    return mm(experts["wo"], h, "ecf,efd->ecd")


def moe_forward(params: Dict[str, Any], x: jax.Array, moe: MoEConfig,
                mlp_type: str = "swiglu", valid: Optional[jax.Array] = None
                ) -> jax.Array:
    """x: (B, S, D) -> (B, S, D).

    valid (B, S) bool: padding tokens of a bucketed/chunked prefill batch.
    Invalid tokens are routed to the overflow slot so they can never
    displace a real token from expert capacity (their output rows are
    garbage either way, but cross-row contamination would not be).
    """
    b, s, d = x.shape
    t = b * s
    e, k = moe.n_experts, moe.top_k
    xf = x.reshape(t, d)

    logits = dense(params["router"], xf.astype(jnp.float32))  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = _gates(probs, moe)

    # flatten (token, slot) assignments and sort by expert
    flat_e = top_e.reshape(-1)                      # (T*k,)
    flat_p = top_p.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(t), k)
    if valid is not None:
        # expert id `e` = overflow: sorts after every real expert, so ranks
        # of valid assignments are exactly what they'd be without padding
        flat_e = jnp.where(jnp.repeat(valid.reshape(t), k), flat_e, e)
    order = jnp.argsort(flat_e, stable=True)
    se, sp, stok = flat_e[order], flat_p[order], flat_tok[order]

    counts = jnp.bincount(se, length=e)             # (E,) — id e dropped
    starts = jnp.cumsum(counts) - counts            # exclusive prefix
    rank = jnp.arange(t * k) - starts[jnp.minimum(se, e - 1)]

    if moe.capacity_factor <= 0:
        cap = t * k  # exact no-drop mode (tests / tiny decode batches)
    else:
        cap = int(max(1, round(t * k / e * moe.capacity_factor)))
    keep = (rank < cap) & (se < e)
    dst = jnp.where(keep, se * cap + jnp.clip(rank, 0, cap - 1), e * cap)

    # scatter tokens into (E*C (+1 overflow), D)
    buf = jnp.zeros((e * cap + 1, d), x.dtype)
    buf = buf.at[dst].add(xf[stok] * keep[:, None].astype(x.dtype))
    xe = buf[:-1].reshape(e, cap, d)

    ye = _expert_ffn(params["experts"], xe)         # (E, C, D)
    yf = ye.reshape(e * cap, d)

    # gather back and combine
    contrib = yf[jnp.clip(dst, 0, e * cap - 1)] * (
        sp * keep.astype(jnp.float32))[:, None].astype(x.dtype)
    y = jnp.zeros((t, d), x.dtype).at[stok].add(contrib)

    if "shared" in params:
        y = y + mlp_forward(params["shared"], xf, mlp_type)
    return y.reshape(b, s, d)


def _gates(probs, moe: MoEConfig):
    """Top-k experts of each token and their weights: the softmax
    probabilities, renormalized over the k winners where the model does."""
    top_p, top_e = jax.lax.top_k(probs, moe.top_k)  # (T, k)
    if moe.norm_topk_prob:
        top_p = top_p / jnp.maximum(jnp.sum(top_p, axis=-1, keepdims=True),
                                    1e-9)
    return top_p, top_e


def _expert_mm(p, x, tile_expert, n_live, tile_rows):
    """Rows sorted by expert (tiles of ``tile_rows``) times their own
    expert's matrix: the grouped kernel for trit-planes, a per-tile
    gather of the stack for float kernels."""
    from repro.core.quantize_model import QuantizedKernel
    from repro.kernels.expert_matmul import expert_matmul
    from repro.models.common import matmul_backend

    k = p["kernel"]
    if isinstance(k, QuantizedKernel):
        return expert_matmul(x, k.t1p, k.t2p, k.alpha, tile_expert, n_live,
                             group_size=k.group_size, tile_rows=tile_rows,
                             backend=matmul_backend(), out_dtype=x.dtype)
    xt = x.reshape(-1, tile_rows, x.shape[-1])
    y = jnp.einsum("tmd,tdf->tmf", xt, k[tile_expert].astype(x.dtype))
    return y.reshape(x.shape[0], -1)


def expert_tiles(flat_e: jax.Array, e: int, tm: int):
    """Assignments (expert ids (A,), ``e`` where not routed) sorted by
    expert into tiles of ``tm`` rows, each expert's rows starting a fresh
    tile. Returns (row of each assignment in the tiled buffer, -1 where
    not routed; tile → expert map, every entry a real expert (tiles past
    the live count repeat the last live tile's, or expert 0 where nothing
    is routed), since the kernel reads that expert's planes for them;
    live tiles (1,); assignments per expert (e,))."""
    from repro.kernels.expert_matmul import n_tiles

    a = flat_e.shape[0]
    counts = jnp.bincount(flat_e, length=e)             # routed only
    tiles = (counts + tm - 1) // tm
    ends = jnp.cumsum(tiles)                            # in tiles
    first = (ends - tiles) * tm                         # an expert's row 0
    order = jnp.argsort(flat_e, stable=True)
    se = jnp.minimum(flat_e[order], e)
    rank = jnp.arange(a) - (jnp.cumsum(counts) - counts)[jnp.minimum(se,
                                                                     e - 1)]
    row_sorted = jnp.where(se < e, first[jnp.minimum(se, e - 1)] + rank, -1)
    row = jnp.zeros((a,), jnp.int32).at[order].set(
        row_sorted.astype(jnp.int32))
    last = jnp.maximum(ends[-1] - 1, 0)
    tile_expert = jnp.searchsorted(
        ends, jnp.minimum(jnp.arange(n_tiles(a, e, tm)), last), side="right")
    return (row, jnp.minimum(tile_expert, e - 1).astype(jnp.int32),
            ends[-1:].astype(jnp.int32), counts)


def moe_serve(params: Dict[str, Any], x: jax.Array, moe: MoEConfig,
              mlp_type: str = "swiglu", valid: Optional[jax.Array] = None):
    """Serving MoE without capacity: x (T, D) -> ((T, D), stats).

    Every valid token (``valid`` (T,) bool; all when None) sends its k
    assignments to their experts, none dropped; invalid tokens send none
    and get only the shared experts. Assignments are sorted by expert
    into tiles of ``TILE_ROWS`` rows, an expert's rows starting a fresh
    tile, and each tile runs its expert's three matrices in the grouped
    ``expert_matmul`` kernel, which skips the tiles past the live count.
    A token's output is the sum of its k expert outputs in top-k order,
    each times its gate, so it depends on that token alone.

    stats (2,) int32: assignments computed and the kernel's tile rows
    (live tiles × tile rows, padding included).
    """
    from repro.kernels.expert_matmul import TILE_ROWS

    t, d = x.shape
    e, k, tm = moe.n_experts, moe.top_k, TILE_ROWS
    with jax.named_scope("moe_route"):
        logits = dense(params["router"], x.astype(jnp.float32))  # (T, E)
        gate, top_e = _gates(jax.nn.softmax(logits, axis=-1), moe)
        ok = (jnp.ones((t,), bool) if valid is None
              else valid.reshape(t))
        flat_e = jnp.where(jnp.repeat(ok, k), top_e.reshape(-1), e)
        row, tile_expert, n_live, counts = expert_tiles(flat_e, e, tm)
        row = row.reshape(t, k)
        n_max = tile_expert.shape[0]
        src = jnp.where(row >= 0, row, n_max * tm).reshape(-1)
        xs = jnp.zeros((n_max * tm, d), x.dtype).at[src].set(
            jnp.repeat(x, k, axis=0), mode="drop")
    with jax.named_scope("moe_experts"):
        ex = params["experts"]
        h = (jax.nn.silu(_expert_mm(ex["wg"], xs, tile_expert, n_live, tm))
             * _expert_mm(ex["wi"], xs, tile_expert, n_live, tm))
        ys = _expert_mm(ex["wo"], h, tile_expert, n_live, tm)
    y = jnp.zeros((t, d), jnp.float32)
    for j in range(k):   # top-k order, a token's own: batch-independent
        r = row[:, j]
        yj = ys[jnp.maximum(r, 0)].astype(jnp.float32) * gate[:, j:j + 1]
        y = y + jnp.where((r >= 0)[:, None], yj, 0.0)
    y = y.astype(x.dtype)
    if "shared" in params:
        y = y + mlp_forward(params["shared"], x, mlp_type)
    stats = jnp.stack([counts.sum(), n_live[0] * tm]).astype(jnp.int32)
    return y, stats


def moe_aux_loss(params: Dict[str, Any], x: jax.Array, moe: MoEConfig) -> jax.Array:
    """Load-balancing auxiliary loss (Switch-style): E · Σ_e f_e · p_e."""
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    logits = dense(params["router"], xf.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_e = jnp.argmax(probs, axis=-1)
    frac = jnp.mean(jax.nn.one_hot(top_e, moe.n_experts), axis=0)
    imp = jnp.mean(probs, axis=0)
    return moe.n_experts * jnp.sum(frac * imp)
