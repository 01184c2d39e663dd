"""Generic decoder LM covering all assigned architectures.

Layer stacking: prefix blocks (unscanned) + `lax.scan` over the repeating
block-pattern period (compile time O(1) in depth; params stacked with a
leading n_periods axis) + automatic remainder blocks. Remat wraps the scan
body. Decode threads the caches through the same scan: recurrent state
(rwkv, rg-lru), which a step rewrites whole, as (xs → ys); the attention
KV ring (or paged pool), of which a step writes a few slots, as one stacked
buffer in the scan's carry that every layer reads and writes in place at
its layer index (``_run_blocks``).

Model API (all pure functions):
  init_params(cfg, key)                        → params
  forward(params, cfg, batch)                  → logits (B, S, V)
  loss_fn(params, cfg, batch)                  → scalar xent (+ MoE aux)
  prefill(params, cfg, batch, capacity)        → (last_logits, state)
  decode_step(params, cfg, state, tokens)      → (logits, state)
  init_decode_state(cfg, batch, capacity)      → state   (zeros; for dry-run
                                                  use jax.eval_shape)
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import mla as mla_mod
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import rwkv6 as rwkv_mod
from repro.models.common import dense, dense_init, dtype_of, norm_init, rms_norm
from repro.sharding.api import constrain


# ---------------------------------------------------------------------------
# block init / forward / prefill / decode dispatch
# ---------------------------------------------------------------------------

def _block_init(kind: str, cfg, key, dtype) -> Dict[str, Any]:
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    if kind == "rwkv":
        return {
            "time_norm": norm_init(d, dtype),
            "time": rwkv_mod.rwkv_time_init(ks[0], d, cfg.rwkv_head_dim, dtype),
            "chan_norm": norm_init(d, dtype),
            "chan": rwkv_mod.rwkv_channel_init(ks[1], d, cfg.d_ff, dtype),
        }
    if kind.startswith("rglru"):
        r = cfg.rglru_width or d
        nb = cfg.rglru_blocks or cfg.n_heads
        return {
            "rec_norm": norm_init(d, dtype),
            "rec": rglru_mod.rglru_init(ks[0], d, r, nb, cfg.conv_width, dtype),
            "mlp_norm": norm_init(d, dtype),
            "mlp": mlp_mod.mlp_init(ks[1], d, cfg.d_ff, cfg.mlp_type, dtype),
        }
    mixer, ffn = kind.split("+")
    p = {
        "attn_norm": norm_init(d, dtype),
        "attn": (mla_mod.mla_init(ks[0], cfg, dtype) if mixer == "mla"
                 else attn.attn_init(ks[0], cfg, dtype)),
        "mlp_norm": norm_init(d, dtype),
    }
    if ffn == "moe":
        p["moe"] = moe_mod.moe_init(ks[1], d, cfg.moe, cfg.mlp_type, dtype)
    else:
        p["mlp"] = mlp_mod.mlp_init(ks[1], d, cfg.d_ff, cfg.mlp_type, dtype)
    return p


def _mixer_window(kind: str, cfg) -> Optional[int]:
    return cfg.window if kind.startswith("local") else None


def _block_forward(kind: str, p, cfg, x, positions):
    if kind == "rwkv":
        y, _ = rwkv_mod.rwkv_time_forward(
            p["time"], rms_norm(p["time_norm"], x, cfg.norm_eps),
            cfg.rwkv_head_dim)
        x = x + y
        y, _ = rwkv_mod.rwkv_channel_forward(
            p["chan"], rms_norm(p["chan_norm"], x, cfg.norm_eps))
        return x + y
    if kind.startswith("rglru"):
        y, _ = rglru_mod.rglru_forward(
            p["rec"], rms_norm(p["rec_norm"], x, cfg.norm_eps),
            cfg.rglru_blocks or cfg.n_heads)
        x = x + y
        y = mlp_mod.mlp_forward(p["mlp"],
                                rms_norm(p["mlp_norm"], x, cfg.norm_eps),
                                cfg.mlp_type)
        return x + y
    # attention blocks
    h = rms_norm(p["attn_norm"], x, cfg.norm_eps)
    if kind.startswith("mla"):
        y = mla_mod.mla_forward(p["attn"], cfg, h, positions,
                                q_chunk=cfg.q_chunk)
    else:
        y = attn.attention_forward(p["attn"], cfg, h, positions,
                                   window=_mixer_window(kind, cfg),
                                   q_chunk=cfg.q_chunk)
    x = x + y
    h = rms_norm(p["mlp_norm"], x, cfg.norm_eps)
    if "moe" in p:
        y = moe_mod.moe_forward(p["moe"], h, cfg.moe, cfg.mlp_type)
    else:
        y = mlp_mod.mlp_forward(p["mlp"], h, cfg.mlp_type)
    return x + y


def _block_cache_init(kind: str, cfg, batch: int, capacity: int, dtype,
                      kv_spec=None):
    if kind == "rwkv":
        h = cfg.d_model // cfg.rwkv_head_dim
        return {
            "x_time": jnp.zeros((batch, cfg.d_model), dtype),
            "wkv": jnp.zeros((batch, h, cfg.rwkv_head_dim, cfg.rwkv_head_dim),
                             jnp.float32),
            "x_chan": jnp.zeros((batch, cfg.d_model), dtype),
        }
    if kind.startswith("rglru"):
        r = cfg.rglru_width or cfg.d_model
        return {
            "h": jnp.zeros((batch, r), jnp.float32),
            "conv": jnp.zeros((batch, cfg.conv_width - 1, r), dtype),
        }
    if kind.startswith("mla"):
        if kv_spec is not None:
            raise ValueError("the latent (MLA) cache has no paged layout")
        return mla_mod.latent_cache_init(cfg, batch, capacity, dtype)
    # recurrent states above are per-row and tiny — kv_spec (the paged KV
    # layout) applies to the attention ring caches only
    return attn.cache_init(cfg, batch, capacity, _mixer_window(kind, cfg),
                           dtype, kv_spec=kv_spec)


def _ffn_serve(p, cfg, h, valid=None):
    """A block's FFN at serving time: (y, MoE stats or None). Experts
    route without capacity (``moe.moe_serve``); ``valid`` (h's leading
    shape) marks the tokens routed at all."""
    if "moe" in p:
        d = h.shape[-1]
        y, stats = moe_mod.moe_serve(
            p["moe"], h.reshape(-1, d), cfg.moe, cfg.mlp_type,
            None if valid is None else valid.reshape(-1))
        return y.reshape(h.shape), stats
    return mlp_mod.mlp_forward(p["mlp"], h, cfg.mlp_type), None


def _block_prefill(kind: str, p, cfg, x, positions, cache, layer=None):
    """Full-seq forward that also fills the decode cache: (x, cache,
    MoE stats or None)."""
    if kind == "rwkv":
        h = rms_norm(p["time_norm"], x, cfg.norm_eps)
        y, (x_last, wkv) = rwkv_mod.rwkv_time_forward(p["time"], h,
                                                      cfg.rwkv_head_dim)
        x = x + y
        h = rms_norm(p["chan_norm"], x, cfg.norm_eps)
        y, xc_last = rwkv_mod.rwkv_channel_forward(p["chan"], h)
        return x + y, {"x_time": x_last, "wkv": wkv, "x_chan": xc_last}, None
    if kind.startswith("rglru"):
        h = rms_norm(p["rec_norm"], x, cfg.norm_eps)
        y, (h_last, conv_state) = rglru_mod.rglru_forward(
            p["rec"], h, cfg.rglru_blocks or cfg.n_heads)
        x = x + y
        y = mlp_mod.mlp_forward(p["mlp"],
                                rms_norm(p["mlp_norm"], x, cfg.norm_eps),
                                cfg.mlp_type)
        return x + y, {"h": h_last, "conv": conv_state}, None
    # attention blocks
    h = rms_norm(p["attn_norm"], x, cfg.norm_eps)
    b, s, _ = x.shape
    pos_bs = jnp.broadcast_to(positions[None, :], (b, s))
    if kind.startswith("mla"):
        y, cache = mla_mod.mla_chunk(p["attn"], cfg, cache, h, pos_bs,
                                     jnp.full((b,), s, jnp.int32),
                                     layer=layer)
    else:
        window = _mixer_window(kind, cfg)
        y, (k, v) = attn.attention_forward(
            p["attn"], cfg, h, positions, window=window, q_chunk=cfg.q_chunk,
            return_kv=True)
        cache = attn.cache_prefill(cfg, cache, k, v, pos_bs, layer=layer)
    x = x + y
    y, stats = _ffn_serve(p, cfg, rms_norm(p["mlp_norm"], x, cfg.norm_eps))
    return x + y, cache, stats


def _freeze_rows(active, new, old):
    """Per-row select: rows with active=False keep their old cache leaves."""
    if active is None:
        return new
    sel = lambda n, o: jnp.where(
        active.reshape((-1,) + (1,) * (n.ndim - 1)), n, o)
    return jax.tree.map(sel, new, old)


def _block_decode(kind: str, p, cfg, cache, x_t, pos, active=None,
                  layer=None):
    """One decode token per row: (x_t, cache, MoE stats or None)."""
    if kind == "rwkv":
        h = rms_norm(p["time_norm"], x_t, cfg.norm_eps)
        y, (x_last, wkv) = rwkv_mod.rwkv_time_decode(
            p["time"], h, cfg.rwkv_head_dim, (cache["x_time"], cache["wkv"]))
        x_t = x_t + y
        h = rms_norm(p["chan_norm"], x_t, cfg.norm_eps)
        y, xc_last = rwkv_mod.rwkv_channel_decode(p["chan"], h, cache["x_chan"])
        new = {"x_time": x_last, "wkv": wkv, "x_chan": xc_last}
        return x_t + y, _freeze_rows(active, new, cache), None
    if kind.startswith("rglru"):
        h = rms_norm(p["rec_norm"], x_t, cfg.norm_eps)
        y, (h_last, conv_state) = rglru_mod.rglru_decode(
            p["rec"], h, cfg.rglru_blocks or cfg.n_heads,
            (cache["h"], cache["conv"]))
        x_t = x_t + y
        y = mlp_mod.mlp_forward(p["mlp"],
                                rms_norm(p["mlp_norm"], x_t, cfg.norm_eps),
                                cfg.mlp_type)
        new = {"h": h_last, "conv": conv_state}
        return x_t + y, _freeze_rows(active, new, cache), None
    h = rms_norm(p["attn_norm"], x_t, cfg.norm_eps)
    if kind.startswith("mla"):
        y, cache = mla_mod.mla_decode(p["attn"], cfg, cache, h, pos,
                                      active=active, layer=layer)
    else:
        y, cache = attn.attention_decode(p["attn"], cfg, cache, h, pos,
                                         window=_mixer_window(kind, cfg),
                                         active=active, layer=layer)
    x_t = x_t + y
    y, stats = _ffn_serve(p, cfg, rms_norm(p["mlp_norm"], x_t, cfg.norm_eps),
                          active)
    return x_t + y, cache, stats


def _recurrent(kind: str) -> bool:
    """Recurrent blocks rewrite their whole (small) state every step; the
    attention blocks keep a KV ring (or paged pool) of which a step writes
    only the slots its tokens land in."""
    return kind == "rwkv" or kind.startswith("rglru")


def has_moe(cfg) -> bool:
    """Whether any layer routes to experts (serving then counts them)."""
    return any(k.endswith("moe") for k in cfg.layer_kinds)


def _add_stats(acc, stats):
    return acc if stats is None else acc + stats


def _run_blocks(cfg, blocks, caches, x, block_fn, *, remat=False):
    """Run the stacked period blocks over ``x``.

    ``block_fn(kind, p, cache, x, layer) -> (x, cache, stats)`` runs one
    block (stats: its MoE counts, or None). Recurrent caches are sliced
    per layer and restacked (``xs → ys``): their step rewrites them whole.
    Ring caches ride in the scan's carry as the whole stack and are passed
    on with the layer index: the kernel reads the layer in place and the
    block writes only the slots its tokens land in, so no ring-sized
    slice, fill or copy exists (a ring in ``xs → ys`` would be copied into
    a fresh stack on every call). Returns (x, caches, summed MoE stats or
    None).
    """
    names = [f"b{i}" for i in range(len(cfg.block_pattern))]
    kinds = dict(zip(names, cfg.block_pattern))
    ring = {n: caches[n] for n in names if not _recurrent(kinds[n])}
    rec = {n: caches[n] for n in names if _recurrent(kinds[n])}
    acc = (jnp.zeros((2,), jnp.int32)
           if any(k.endswith("moe") for k in cfg.block_pattern) else None)

    def period_fn(carry, xs):
        x, ring, acc = carry
        period_params, rec_p, layer = xs
        ring, new_rec = dict(ring), {}
        for n in names:
            if n in ring:
                x, ring[n], st = block_fn(kinds[n], period_params[n],
                                          ring[n], x, layer)
            else:
                x, new_rec[n], st = block_fn(kinds[n], period_params[n],
                                             rec_p[n], x, None)
            acc = _add_stats(acc, st)
        return (constrain(x, "hidden"), ring, acc), new_rec

    if remat:
        period_fn = jax.checkpoint(period_fn)
    if cfg.scan_layers:
        (x, ring, acc), rec = jax.lax.scan(
            period_fn, (x, ring, acc),
            (blocks, rec, jnp.arange(cfg.n_periods, dtype=jnp.int32)))
    else:  # unrolled (exact per-layer HLO costs; dry-run cost variants)
        outs = []
        for i in range(cfg.n_periods):
            sl = lambda a: a[i]
            (x, ring, acc), nc = period_fn(
                (x, ring, acc), (jax.tree.map(sl, blocks),
                                 jax.tree.map(sl, rec), i))
            outs.append(nc)
        rec = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    return x, {**ring, **rec}, acc


# ---------------------------------------------------------------------------
# model init
# ---------------------------------------------------------------------------

def init_params(cfg, key) -> Dict[str, Any]:
    dtype = dtype_of(cfg.param_dtype)
    n_extra = 3 + len(cfg.prefix_pattern) + len(cfg.remainder_pattern)
    keys = jax.random.split(key, cfg.n_periods * cfg.period + n_extra)
    ki = iter(range(len(keys)))

    params: Dict[str, Any] = {}
    if cfg.embed_inputs:
        params["embed"] = {
            "embedding": (jax.random.normal(keys[next(ki)],
                                            (cfg.vocab_size, cfg.d_model))
                          * 0.02).astype(dtype)
        }
    params["prefix"] = {
        f"p{i}": _block_init(kind, cfg, keys[next(ki)], dtype)
        for i, kind in enumerate(cfg.prefix_pattern)
    }
    # stacked period blocks: one stack per period position
    blocks = {}
    for pidx, kind in enumerate(cfg.block_pattern):
        per = [_block_init(kind, cfg, keys[next(ki)], dtype)
               for _ in range(cfg.n_periods)]
        blocks[f"b{pidx}"] = jax.tree.map(lambda *xs: jnp.stack(xs), *per)
    params["blocks"] = blocks
    params["suffix"] = {
        f"s{i}": _block_init(kind, cfg, keys[next(ki)], dtype)
        for i, kind in enumerate(cfg.remainder_pattern)
    }
    params["final_norm"] = norm_init(cfg.d_model, dtype)
    params["lm_head"] = dense_init(keys[next(ki)], cfg.d_model, cfg.vocab_size,
                                   dtype=dtype)
    return params


# ---------------------------------------------------------------------------
# forward / loss
# ---------------------------------------------------------------------------

def _embed(params, cfg, batch):
    adt = dtype_of(cfg.activation_dtype)
    if cfg.embed_inputs:
        x = jnp.take(params["embed"]["embedding"], batch["tokens"], axis=0)
    else:
        x = batch["embeddings"]
    return constrain(x.astype(adt), "hidden")


def forward(params, cfg, batch) -> jax.Array:
    """Training/eval forward. batch: {"tokens"|"embeddings": (B, S[, D])}."""
    x = _embed(params, cfg, batch)
    s = x.shape[1]
    positions = jnp.arange(s)

    for i, kind in enumerate(cfg.prefix_pattern):
        x = _block_forward(kind, params["prefix"][f"p{i}"], cfg, x, positions)

    if cfg.n_periods:
        def period_fn(x, period_params):
            for pidx, kind in enumerate(cfg.block_pattern):
                x = _block_forward(kind, period_params[f"b{pidx}"], cfg, x,
                                   positions)
            return constrain(x, "hidden"), None

        if cfg.remat == "full":
            period_fn = jax.checkpoint(period_fn)
        if cfg.scan_layers:
            x, _ = jax.lax.scan(period_fn, x, params["blocks"])
        else:  # unrolled (exact per-layer HLO costs; dry-run cost variants)
            for i in range(cfg.n_periods):
                x, _ = period_fn(x, jax.tree.map(lambda a: a[i],
                                                 params["blocks"]))

    for i, kind in enumerate(cfg.remainder_pattern):
        x = _block_forward(kind, params["suffix"][f"s{i}"], cfg, x, positions)

    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    logits = dense(params["lm_head"], x)
    return constrain(logits, "logits")


def loss_fn(params, cfg, batch) -> jax.Array:
    """Mean next-token cross-entropy (+ MoE aux loss if applicable)."""
    logits = forward(params, cfg, batch).astype(jnp.float32)
    labels = batch["labels"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    loss = jnp.mean(logz - gold)
    return loss


# ---------------------------------------------------------------------------
# decode state / prefill / decode_step
# ---------------------------------------------------------------------------

def init_decode_state(cfg, batch: int, capacity: int, *,
                      kv_spec=None) -> Dict[str, Any]:
    """Zeroed decode state. ``kv_spec = {"page_size": ps, "max_pages": n}``
    selects the paged KV layout for every attention layer (pool leaves
    ``pages_*`` + per-row ``table``); None keeps the per-row ring."""
    adt = dtype_of(cfg.activation_dtype)

    def stack_cache(kind):
        # broadcast (not zeros!) so sentinel values (e.g. pos = -1) survive;
        # one jitted fill writes each stack once, where eager code would
        # hold the layer, its broadcast and a copy of the stack at once
        return jax.jit(lambda: jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (cfg.n_periods,) + a.shape),
            _block_cache_init(kind, cfg, batch, capacity, adt, kv_spec)))()

    return {
        "pos": jnp.zeros((batch,), jnp.int32),
        "prefix": {f"p{i}": _block_cache_init(kind, cfg, batch, capacity,
                                              adt, kv_spec)
                   for i, kind in enumerate(cfg.prefix_pattern)},
        "blocks": {f"b{pidx}": stack_cache(kind)
                   for pidx, kind in enumerate(cfg.block_pattern)},
        "suffix": {f"s{i}": _block_cache_init(kind, cfg, batch, capacity,
                                              adt, kv_spec)
                   for i, kind in enumerate(cfg.remainder_pattern)},
    }


def _serve_layers(params, cfg, caches, x, block_fn, *, remat=False):
    """Prefix blocks, the scanned period and the remainder over ``x``
    with their caches (``caches["prefix" | "blocks" | "suffix"]``).
    Returns (x, {"prefix", "blocks", "suffix"} caches, summed MoE stats
    (2,) int32 — assignments routed, expert tile rows — or None for a
    model without experts)."""
    out = {"prefix": {}, "blocks": None, "suffix": {}}
    stats = jnp.zeros((2,), jnp.int32) if has_moe(cfg) else None
    for part, short, kinds in (("prefix", "p", cfg.prefix_pattern),
                               ("suffix", "s", cfg.remainder_pattern)):
        if part == "suffix" and cfg.n_periods:
            x, out["blocks"], st = _run_blocks(
                cfg, params["blocks"], caches["blocks"], x, block_fn,
                remat=remat)
            stats = _add_stats(stats, st)
        for i, kind in enumerate(kinds):
            x, out[part][f"{short}{i}"], st = block_fn(
                kind, params[part][f"{short}{i}"],
                caches[part][f"{short}{i}"], x, None)
            stats = _add_stats(stats, st)
    return x, out, stats


def prefill(params, cfg, batch, capacity: int) -> Tuple[jax.Array, Dict]:
    """Process a full prompt; return (last-position logits, decode state)."""
    x = _embed(params, cfg, batch)
    b, s, _ = x.shape
    positions = jnp.arange(s)
    state = init_decode_state(cfg, b, capacity)
    x, caches, _ = _serve_layers(
        params, cfg, state, x,
        lambda kind, p, cache, x, layer: _block_prefill(
            kind, p, cfg, x, positions, cache, layer),
        remat=cfg.remat == "full")
    state.update(caches)

    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    logits = dense(params["lm_head"], x[:, -1])
    state["pos"] = jnp.full((b,), s, jnp.int32)
    return constrain(logits, "decode_logits"), state


def _block_prefill_chunk(kind: str, p, cfg, x, positions, lengths, valid,
                         cache, layer=None):
    """Chunk forward that continues from and updates an existing cache.

    x: (B, L, D) right-padded; positions: (B, L) absolute per row;
    lengths: (B,) valid counts (0 = no-op row); valid: (B, L) bool.
    Returns (x, cache, MoE stats or None).
    """
    if kind == "rwkv":
        h = rms_norm(p["time_norm"], x, cfg.norm_eps)
        y, (x_last, wkv) = rwkv_mod.rwkv_time_forward(
            p["time"], h, cfg.rwkv_head_dim,
            state=(cache["x_time"], cache["wkv"]), mask=valid)
        x = x + y
        h = rms_norm(p["chan_norm"], x, cfg.norm_eps)
        y, xc_last = rwkv_mod.rwkv_channel_forward(
            p["chan"], h, state=cache["x_chan"], mask=valid)
        return (x + y, {"x_time": x_last, "wkv": wkv, "x_chan": xc_last},
                None)
    if kind.startswith("rglru"):
        h = rms_norm(p["rec_norm"], x, cfg.norm_eps)
        y, (h_last, conv_state) = rglru_mod.rglru_forward(
            p["rec"], h, cfg.rglru_blocks or cfg.n_heads,
            state=(cache["h"], cache["conv"]), mask=valid)
        x = x + y
        y = mlp_mod.mlp_forward(p["mlp"],
                                rms_norm(p["mlp_norm"], x, cfg.norm_eps),
                                cfg.mlp_type)
        return x + y, {"h": h_last, "conv": conv_state}, None
    # attention blocks
    h = rms_norm(p["attn_norm"], x, cfg.norm_eps)
    if kind.startswith("mla"):
        y, cache = mla_mod.mla_chunk(p["attn"], cfg, cache, h, positions,
                                     lengths, layer=layer)
    else:
        y, cache = attn.attention_prefill_chunk(
            p["attn"], cfg, cache, h, positions, lengths,
            window=_mixer_window(kind, cfg), layer=layer)
    x = x + y
    y, stats = _ffn_serve(p, cfg, rms_norm(p["mlp_norm"], x, cfg.norm_eps),
                          valid)
    return x + y, cache, stats


def prefill_chunk(params, cfg, state, batch, lengths) -> Tuple[jax.Array, Dict]:
    """Padded-batch / chunked prefill through one fixed-shape compiled fn.

    batch: {"tokens": (B, L)} right-padded to the bucket length L;
    lengths: (B,) int32 — row r consumes positions ``state['pos'][r] ..
    state['pos'][r]+lengths[r]-1`` of its prompt (lengths[r]=0 makes the row
    a complete no-op, so free/decoding rows ride along untouched).

    One compiled function serves every (admission batch, chunk offset) at a
    given bucket L — the serving engine's prefill compile cache becomes
    O(log capacity) instead of one entry per distinct prompt length. A long
    prompt is fed through repeated calls (cache write offset = state pos),
    interleaving with decode chunks instead of blocking them.

    Returns (logits at each row's last valid token (B, V), updated state).
    Logits of rows with lengths[r] == 0 are garbage — callers ignore them.
    A model with experts adds ``moe_stats`` to the state it returns: this
    call's (assignments routed, expert tile rows), int32 (2,); it is not
    part of the decode state, and a caller drops it before the next call.
    """
    x = _embed(params, cfg, batch)
    b, L, _ = x.shape
    pos0 = state["pos"]
    positions = pos0[:, None] + jnp.arange(L)[None, :]
    valid = jnp.arange(L)[None, :] < lengths[:, None]
    x, new_state, stats = _serve_layers(
        params, cfg, state, x,
        lambda kind, p, cache, x, layer: _block_prefill_chunk(
            kind, p, cfg, x, positions, lengths, valid, cache, layer),
        remat=cfg.remat == "full")
    new_state["pos"] = pos0 + lengths.astype(jnp.int32)
    if stats is not None:
        new_state["moe_stats"] = stats

    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    idx = jnp.maximum(lengths - 1, 0).astype(jnp.int32)
    x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
    logits = dense(params["lm_head"], x_last)
    return constrain(logits, "decode_logits"), new_state


def decode_step(params, cfg, state, tokens, active=None) -> Tuple[jax.Array, Dict]:
    """One decode step. tokens: (B,) int32 (or (B, D) embeddings if stub).

    active (B,) bool: rows with active=False are frozen — position and every
    cache leaf pass through unchanged, so a decode dispatch can share the
    batch state with rows that are mid-(chunked-)prefill or already free.
    Rows with active=False are not routed to experts; ``moe_stats`` as in
    ``prefill_chunk``.
    """
    adt = dtype_of(cfg.activation_dtype)
    if cfg.embed_inputs:
        x = jnp.take(params["embed"]["embedding"], tokens, axis=0)
    else:
        x = tokens
    x = x.astype(adt)
    pos = state["pos"]
    new_pos = pos + (active.astype(jnp.int32) if active is not None else 1)
    x, new_state, stats = _serve_layers(
        params, cfg, state, x,
        lambda kind, p, cache, x, layer: _block_decode(
            kind, p, cfg, cache, x, pos, active, layer))
    new_state["pos"] = new_pos
    if stats is not None:
        new_state["moe_stats"] = stats

    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    logits = dense(params["lm_head"], x)
    return constrain(logits, "decode_logits"), new_state
