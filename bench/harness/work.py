"""The work that served tokens need: operations and bytes per dispatch.

Counted from the configuration's published shapes and the engine's own
dispatch records, never from what an implementation touches:

* rows: only the live rows of a dispatch (a prefill row's tokens this
  chunk; a decode row's tokens this dispatch), never padding or idle
  slots;
* attention: each query reads the live positions of its own row,
  min(position, capacity) of them, and its own chunk's keys;
* weights: each matrix's stored representation (two 2-bit planes and its
  α scales), read once per call; other parameters at their dtype, once
  per call; the embedding only at the rows gathered;
* recurrent state: read and written once per live row per call;
* activations: the live rows' inputs and outputs of each matmul.

What differs between architectures (each layer's matrices, other
parameters and state, and the calls of kernels other than the ternary
matmul) is counted by the configuration's architecture module, layer by
layer (``Layer``); this module keeps the loop over steps and layers, the
output head and the embedding rows.

A call's roofline time is the larger of its operations over the chip's
peak rate and its bytes over the chip's bandwidth; sums of roofline time
run over calls, so each call is bounded by its own limit.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Sequence, Tuple

BF16 = 2


@dataclasses.dataclass
class Tally:
    """Operations, bytes, and roofline seconds of one component."""
    flops: float = 0.0
    bytes: float = 0.0
    roofline_s: float = 0.0
    compute_bound_s: float = 0.0      # share of roofline_s bound by FLOP/s
    calls: int = 0

    def add(self, flops: float, nbytes: float, peaks: Dict) -> None:
        tf = flops / peaks["bf16_flops_per_s"]
        tb = nbytes / peaks["hbm_bytes_per_s"]
        self.flops += flops
        self.bytes += nbytes
        self.roofline_s += max(tf, tb)
        if tf >= tb:
            self.compute_bound_s += tf
        self.calls += 1

    def merge(self, other: "Tally") -> None:
        for f in dataclasses.fields(self):
            setattr(self, f.name,
                    getattr(self, f.name) + getattr(other, f.name))


@dataclasses.dataclass
class Row:
    """One live row of a dispatch: its first position and token count."""
    start: int
    n: int
    logits: int = 0      # tokens of this row that need the output head


@dataclasses.dataclass(frozen=True)
class Layer:
    """One layer's work in one step of a dispatch, as its architecture
    module counts it (``layer(model, i)``). A ternary matrix is (d_in,
    d_out), or (d_in, d_out, share) where it sees only that share of the
    step's rows (a routed expert: experts per token / experts)."""
    matrices: Sequence[Tuple]             # each ternary matrix
    dense_param_bytes: int                # other parameters read per call
    dense_flops_per_token: float = 0.0    # outside the matmuls and kernels
    state_bytes_per_row: int = 0          # recurrent state, read and written
    # kernel -> its call's (operations, bytes) over a step's live rows
    kernels: Dict[str, Callable[[Sequence[Row]], Tuple[float, float]]] = \
        dataclasses.field(default_factory=dict)


class Model:
    """Shapes of one configuration file, in its published vocabulary, and
    the work of each of its layers as its architecture module counts it."""

    def __init__(self, c: Dict, arch):
        self.config = c
        self.d = c["hidden_size"]
        self.n_layers = c["num_hidden_layers"]
        self.vocab = c["vocab_size"]
        q = c["quantization"]
        self.group = q["group_size"]
        self.alpha_bytes = {"float32": 4, "bfloat16": 2}[q["alpha_dtype"]]
        self.kv_bytes = {"bfloat16": 2, "int8": 1}[
            q.get("kv_cache_dtype", "bfloat16")]
        self.capacity = c["engine"]["capacity"]
        self.kernels = ("ternary_matmul", *getattr(arch, "KERNELS", {}),
                        "other")
        self.layers = [arch.layer(self, i) for i in range(self.n_layers)]
        unknown = {k for layer in self.layers for k in layer.kernels
                   } - set(self.kernels)
        if unknown:
            raise KeyError(f"{arch.__name__} counts kernels {sorted(unknown)}"
                           f" that its KERNELS does not name")

    def tallies(self) -> Dict[str, Tally]:
        """One empty tally per kernel, and ``other``."""
        return {k: Tally() for k in self.kernels}

    def ternary_bytes(self, d_in: int, d_out: int) -> int:
        return (2 * d_out * d_in // 4
                + d_out * (d_in // self.group) * 2 * self.alpha_bytes)


def _ternary(model: Model, t: Tally, m: float, d_in: int, d_out: int,
             peaks: Dict) -> None:
    if m <= 0:
        return
    t.add(2.0 * m * d_in * d_out,
          model.ternary_bytes(d_in, d_out) + BF16 * m * (d_in + d_out), peaks)


def attention(model: Model, rows: Sequence[Row], heads: int, kv_heads: int,
              hd: int) -> Tuple[float, float]:
    """(operations, bytes) of one layer's attention call over rows whose
    queries are positions start .. start + n - 1, each seeing every live
    key up to itself, with ``kv_heads`` heads of ``hd`` in the KV cache."""
    flops = nbytes = 0.0
    per_pos = 2 * kv_heads * hd * model.kv_bytes
    cap = model.capacity
    for r in rows:
        if r.n <= 0:
            continue
        keys = sum(min(p + 1, cap) for p in range(r.start, r.start + r.n))
        flops += 4.0 * heads * hd * keys
        nbytes += min(r.start, cap) * per_pos          # the ring before
        nbytes += r.n * per_pos                          # the chunk's k, v
        nbytes += 2 * r.n * heads * hd * BF16            # q in, out
    return flops, nbytes


def dispatch(model: Model, steps: Sequence[Sequence[Row]],
             peaks: Dict) -> Dict[str, Tally]:
    """Work of one dispatch, given its live rows at each of its steps (a
    prefill dispatch is one step whose rows carry several tokens; a decode
    dispatch is one step per token generated)."""
    out = model.tallies()
    for rows in steps:
        rows = [r for r in rows if r.n > 0]
        if not rows:
            continue
        m = sum(r.n for r in rows)
        m_head = sum(r.logits for r in rows)
        for layer in model.layers:
            for d_in, d_out, *share in layer.matrices:
                _ternary(model, out["ternary_matmul"],
                         m * share[0] if share else m, d_in, d_out, peaks)
            for kernel, call in layer.kernels.items():
                flops, nbytes = call(rows)
                if flops:
                    out[kernel].add(flops, nbytes, peaks)
            out["other"].add(
                m * layer.dense_flops_per_token,
                layer.dense_param_bytes
                + 2 * len(rows) * layer.state_bytes_per_row, peaks)
        _ternary(model, out["ternary_matmul"], m_head, model.d, model.vocab,
                 peaks)
        out["other"].add(0.0, m * model.d * BF16, peaks)   # embedding rows
    return out


def total(tallies: Dict[str, Tally]) -> Tally:
    t = Tally()
    for v in tallies.values():
        t.merge(v)
    return t
