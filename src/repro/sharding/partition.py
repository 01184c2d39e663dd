"""Sharding rules: param / batch / decode-state PartitionSpecs.

Mesh axes (launch/mesh.py):
  single pod:  ("data", "model") = (16, 16)
  multi-pod:   ("pod", "data", "model") = (2, 16, 16)

Strategy (DESIGN.md §5):
  * TP over "model": attention heads, FFN hidden, vocab, MoE experts
    (expert-parallel when E % tp == 0, else FFN-dim TP),
  * FSDP/ZeRO over "data" (+"pod"): the non-TP dim of every large matrix and
    its optimizer moments,
  * batch over ("pod","data"),
  * long-context decode: KV-cache sequence dim sharded over "data" (SP).

Every rule is divisibility-checked against the actual mesh axis sizes and
falls back to replication for a dim that does not divide — so the same rule
set serves full configs, smoke configs, and any elastic mesh shape.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.quantize_model import QuantizedKernel

Axis = Union[None, str, Tuple[str, ...]]

# ---------------------------------------------------------------------------
# parallelism policy (hillclimb iteration 2, EXPERIMENTS.md §Perf):
#   "tp"       — TP over "model" + FSDP over "data"(+"pod")  [default]
#   "fsdp_all" — no TP; FSDP/ZeRO-3 + batch over EVERY mesh axis. For small
#                dense models at large token batches, TP's per-layer
#                activation all-reduces dwarf FSDP's param all-gathers —
#                fsdp_all trades ~6 (B,S,D)-sized all-reduces per layer for
#                ~3× param-bytes of all-gathers.
# ---------------------------------------------------------------------------
import contextlib
import threading

_policy_state = threading.local()


def current_policy() -> str:
    return getattr(_policy_state, "policy", "tp")


@contextlib.contextmanager
def parallelism_policy(policy: str):
    assert policy in ("tp", "fsdp_all")
    prev = current_policy()
    _policy_state.policy = policy
    try:
        yield
    finally:
        _policy_state.policy = prev


def _axis_size(mesh: Mesh, axis: Axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, str):
        return mesh.shape[axis]
    return int(np.prod([mesh.shape[a] for a in axis]))


def _maybe(mesh: Mesh, axis: Axis, dim: int) -> Axis:
    """Use `axis` for a dim only if the dim divides the axis size."""
    size = _axis_size(mesh, axis)
    return axis if (size > 1 and dim % size == 0) else None


def fsdp_axes(mesh: Mesh) -> Axis:
    if current_policy() == "fsdp_all":
        return tuple(mesh.axis_names)  # ZeRO-3 over every axis
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def dp_axes(mesh: Mesh) -> Axis:
    return fsdp_axes(mesh)


def tp_axis(mesh: Mesh) -> Axis:
    return None if current_policy() == "fsdp_all" else "model"


# ---------------------------------------------------------------------------
# parameter rules
# ---------------------------------------------------------------------------

def _dense_kernel_rule(path: str, shape, mesh: Mesh) -> P:
    """PartitionSpec for a 2/3-D dense kernel identified by its path."""
    fsdp = fsdp_axes(mesh)
    tp = tp_axis(mesh)

    def spec2(a: Axis, b: Axis, d0: int, d1: int) -> P:
        return P(_maybe(mesh, a, d0), _maybe(mesh, b, d1))

    d = shape[-2], shape[-1]
    if "embed/embedding" in path:
        return spec2(tp, fsdp, *d)
    if "lm_head" in path:
        return spec2(fsdp, tp, *d)
    if "/experts/" in path:
        e = shape[0]
        ep = tp is not None and e % mesh.shape[tp] == 0
        if path.endswith("wo/kernel"):
            return (P(tp, None, _maybe(mesh, fsdp, d[1])) if ep
                    else P(None, _maybe(mesh, tp, d[0]),
                           _maybe(mesh, fsdp, d[1])))
        return (P(tp, _maybe(mesh, fsdp, d[0]), None) if ep
                else P(None, _maybe(mesh, fsdp, d[0]),
                       _maybe(mesh, tp, d[1])))
    if "router" in path:
        return P(None, None)
    if "/chan/wv" in path or path.endswith("wo/kernel"):
        # contraction dim over TP, output dim over FSDP (row-parallel)
        return spec2(tp, fsdp, *d)
    if any(k in path for k in ("wq", "wk", "wv", "wg", "wi", "wr",
                               "wx", "wgate")):
        # column-parallel: input over FSDP, output over TP
        return spec2(fsdp, tp, *d)
    return P(*([None] * len(shape)))


def _param_rule(path: str, leaf, mesh: Mesh) -> P:
    fsdp = fsdp_axes(mesh)
    shape = leaf.shape
    rank = len(shape)

    stacked = "/blocks/" in path  # scanned stacks carry a leading layer dim

    def finish(spec: P) -> P:
        if stacked:
            return P(*((None,) + tuple(spec)))
        return spec

    core_shape = shape[1:] if stacked else shape
    core_rank = len(core_shape)

    if path.endswith("kernel") and core_rank in (2, 3):
        return finish(_dense_kernel_rule(path, core_shape, mesh))
    if path.endswith("embedding") and core_rank == 2:
        # vocab-sharded embedding table (leaf is "embedding", not "kernel")
        return finish(_dense_kernel_rule(path, core_shape, mesh))
    if path.endswith("bias") and core_rank == 1:
        # biases of TP-column-parallel layers live on the TP'd output dim
        if any(k in path for k in ("wq/", "wk/", "wv/", "wg/", "wi/",
                                   "wx/", "wgate/")):
            return finish(P(_maybe(mesh, tp_axis(mesh), core_shape[0])))
        return finish(P(None))
    if path.endswith("lam") and core_rank == 1:
        return finish(P(_maybe(mesh, tp_axis(mesh), core_shape[0])))
    if "conv/w" in path and core_rank == 2:
        return finish(P(None, _maybe(mesh, tp_axis(mesh), core_shape[1])))
    return finish(P(*([None] * core_rank)))


def _quantized_specs(path: str, qk: QuantizedKernel, mesh: Mesh, stacked: bool):
    """Derive trit-plane/scale specs from the dense kernel's rule.

    Buffer layouts: planes (lead..., d_out, d_in // 4), scales
    (lead..., d_out, d_in // G, 2). Leading dims: scan stack (L) and/or
    MoE experts (E) — E shards over "model" when divisible (EP)."""
    lead = qk.t1p.shape[:-2]
    tp, fsdp = tp_axis(mesh), fsdp_axes(mesh)

    if "/experts/" in path:
        e = lead[-1]
        ep = (tp is not None and mesh.shape[tp] > 1
              and e % mesh.shape[tp] == 0)
        e_ax = tp if ep else None
        if path.endswith("wo/kernel"):   # dense (E, fe, d)
            out_ax, in_ax = (fsdp, None) if ep else (fsdp, tp)
        else:                            # wi/wg: dense (E, d, fe)
            out_ax, in_ax = (None, fsdp) if ep else (tp, fsdp)
        head = (None,) * (len(lead) - 1) + (e_ax,)
    else:
        dense_spec = _dense_kernel_rule(path, (qk.d_in, qk.d_out), mesh)
        in_ax, out_ax = dense_spec[-2], dense_spec[-1]
        head = (None,) * len(lead)

    plane = P(*head, _maybe(mesh, out_ax, qk.d_out),
              _maybe(mesh, in_ax, qk.d_in // 4))
    alpha = P(*head, _maybe(mesh, out_ax, qk.d_out), None, None)
    return plane, plane, alpha


def param_pspecs(params: Any, mesh: Mesh) -> Any:
    """PartitionSpec tree matching a params tree (dense or quantized)."""

    def walk(node, path):
        if isinstance(node, QuantizedKernel):
            stacked = node.t1p.ndim == 3
            t1s, t2s, als = _quantized_specs(path, node, mesh, stacked)
            return QuantizedKernel(t1s, t2s, als, node.d_in, node.d_out,
                                   node.group_size)
        if isinstance(node, dict):
            return {k: walk(v, f"{path}/{k}") for k, v in node.items()}
        return _param_rule(path, node, mesh)

    return walk(params, "")


# ---------------------------------------------------------------------------
# batch / decode-state rules
# ---------------------------------------------------------------------------

def batch_pspecs(batch: Any, mesh: Mesh) -> Any:
    dp = dp_axes(mesh)

    def rule(leaf):
        b = leaf.shape[0]
        return P(*( (_maybe(mesh, dp, b),) + (None,) * (leaf.ndim - 1)))

    return jax.tree.map(rule, batch)


def state_pspecs(state: Any, mesh: Mesh, *, sequence_sharded: bool) -> Any:
    """Decode-state specs. sequence_sharded=True → long-context SP mode."""
    dp = dp_axes(mesh)

    def walk(node, path):
        if isinstance(node, dict):
            return {k: walk(v, f"{path}/{k}") for k, v in node.items()}
        shape = node.shape
        stacked = "/blocks/" in path
        core = shape[1:] if stacked else shape
        name = path.rsplit("/", 1)[-1]

        if name in ("k_scale", "v_scale"):  # (KV, B, cap) int8 scales
            if sequence_sharded:
                spec = (None, None, _maybe(mesh, "data", core[2]))
            else:
                spec = (None, _maybe(mesh, dp, core[1]),
                        _maybe(mesh, "model", core[2]))
        elif name in ("k", "v"):          # (B, cap, KV·hd)
            if sequence_sharded:
                spec = (None, _maybe(mesh, "data", core[1]), None)
            else:
                # batch over dp AND cache sequence over "model" (KV heads are
                # too few to TP; slot-sharding divides cache HBM by tp)
                spec = (_maybe(mesh, dp, core[0]),
                        _maybe(mesh, "model", core[1]), None)
        elif name == "pos" and len(core) == 2:   # ring positions (B, cap)
            if sequence_sharded:
                spec = (None, _maybe(mesh, "data", core[1]))
            else:
                spec = (_maybe(mesh, dp, core[0]),
                        _maybe(mesh, "model", core[1]))
        elif name == "pos":                      # top-level (B,)
            spec = (_maybe(mesh, dp, core[0]),)
        elif name == "wkv":                      # (B, H, hd, hd)
            spec = (_maybe(mesh, dp, core[0]), None, None, None)
        elif name in ("h",):                     # (B, R)
            spec = (_maybe(mesh, dp, core[0]),
                    _maybe(mesh, "model", core[1]))
        elif name == "conv":                     # (B, W-1, R)
            spec = (_maybe(mesh, dp, core[0]), None,
                    _maybe(mesh, "model", core[2]))
        else:                                    # x_time/x_chan etc. (B, D)
            spec = ((_maybe(mesh, dp, core[0]),) +
                    (None,) * (len(core) - 1))
        if stacked:
            spec = (None,) + spec
        return P(*spec)

    return walk(state, "")


def activation_rules(mesh: Mesh, *, mode: str) -> Dict[str, P]:
    """Rules consumed by repro.sharding.api.constrain inside the models."""
    dp = dp_axes(mesh)
    tp = tp_axis(mesh)
    if mode == "train" or mode == "prefill":
        return {
            "hidden": P(dp, None, None),
            "logits": P(dp, None, tp),
            "decode_logits": P(dp, tp),
        }
    if mode == "decode":
        return {"decode_logits": P(dp, tp), "hidden": None}
    if mode == "decode_long":   # batch=1: only vocab TP applies
        return {"decode_logits": P(None, tp), "hidden": None}
    raise ValueError(mode)


def named(tree: Any, mesh: Mesh) -> Any:
    """PartitionSpec tree -> NamedSharding tree."""
    def conv(node):
        if isinstance(node, P):
            return NamedSharding(mesh, node)
        return node

    # QuantizedKernel nodes hold specs in their children; map over leaves
    return jax.tree.map(conv, tree,
                        is_leaf=lambda x: isinstance(x, P))
