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
* recurrent state (rwkv6): read and written once per live row per call;
* activations: the live rows' inputs and outputs of each matmul.

A call's roofline time is the larger of its operations over the chip's
peak rate and its bytes over the chip's bandwidth; sums of roofline time
run over calls, so each call is bounded by its own limit.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Tuple

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


class Model:
    """Shapes of one configuration file, in its published vocabulary."""

    def __init__(self, c: Dict):
        self.kind = c["model_type"]
        self.d = c["hidden_size"]
        self.n_layers = c["num_hidden_layers"]
        self.vocab = c["vocab_size"]
        self.ff = c["intermediate_size"]
        q = c["quantization"]
        self.group = q["group_size"]
        self.alpha_bytes = {"float32": 4, "bfloat16": 2}[q["alpha_dtype"]]
        self.kv_bytes = {"bfloat16": 2, "int8": 1}[
            q.get("kv_cache_dtype", "bfloat16")]
        self.capacity = c["engine"]["capacity"]
        if self.kind == "qwen2":
            self.heads = c["num_attention_heads"]
            self.kv_heads = c["num_key_value_heads"]
            self.hd = self.d // self.heads
        elif self.kind == "rwkv6":
            self.hd = c["head_size"]
            self.heads = self.d // self.hd
        else:
            raise KeyError(f"no work model for {self.kind!r}")

    # ---- per-layer parameters --------------------------------------------
    def matrices(self) -> List[Tuple[int, int]]:
        """(d_in, d_out) of each ternary matrix of one layer."""
        d, ff = self.d, self.ff
        if self.kind == "qwen2":
            hq, hkv = self.heads * self.hd, self.kv_heads * self.hd
            return [(d, hq), (d, hkv), (d, hkv), (hq, d),
                    (d, ff), (d, ff), (ff, d)]
        return [(d, d)] * 5 + [(d, ff), (ff, d), (d, d)]

    def dense_param_bytes(self) -> int:
        """Non-ternary parameters one layer reads per call."""
        d = self.d
        if self.kind == "qwen2":
            bias = self.heads * self.hd + 2 * self.kv_heads * self.hd
            return BF16 * (bias + 2 * d)
        lora = d * 160 + 160 * d + d * 64 + 64 * d
        vecs = d + 5 * d + d + self.heads * self.hd + d + 2 * d + 2 * d
        return BF16 * (lora + vecs)

    def dense_flops_per_token(self) -> float:
        """Per layer, per token: operations outside the ternary matmuls
        and attention (rwkv6: token-shift and decay LoRAs, WKV update)."""
        if self.kind == "qwen2":
            return 0.0
        lora_macs = self.d * 160 + 160 * self.d + self.d * 64 + 64 * self.d
        return 2.0 * lora_macs + 7.0 * self.heads * self.hd * self.hd

    def state_bytes_per_row(self) -> int:
        """Recurrent state of one row, one layer (read + write counted by
        the caller)."""
        if self.kind == "qwen2":
            return 0
        return self.heads * self.hd * self.hd * 4 + 2 * self.d * BF16

    def ternary_bytes(self, d_in: int, d_out: int) -> int:
        return (2 * d_out * d_in // 4
                + d_out * (d_in // self.group) * 2 * self.alpha_bytes)

    def kv_bytes_per_pos(self) -> int:
        return 2 * self.kv_heads * self.hd * self.kv_bytes


@dataclasses.dataclass
class Row:
    """One live row of a dispatch: its first position and token count."""
    start: int
    n: int
    logits: int = 0      # tokens of this row that need the output head


def _ternary(model: Model, t: Tally, m: int, d_in: int, d_out: int,
             peaks: Dict) -> None:
    if m <= 0:
        return
    t.add(2.0 * m * d_in * d_out,
          model.ternary_bytes(d_in, d_out) + BF16 * m * (d_in + d_out), peaks)


def _attention(model: Model, t: Tally, rows: Sequence[Row],
               peaks: Dict) -> None:
    """One layer's attention call over rows whose queries are positions
    start .. start + n - 1, each seeing every live key up to itself."""
    flops = nbytes = 0.0
    per_pos = model.kv_bytes_per_pos()
    cap = model.capacity
    for r in rows:
        if r.n <= 0:
            continue
        keys = sum(min(p + 1, cap) for p in range(r.start, r.start + r.n))
        flops += 4.0 * model.heads * model.hd * keys
        nbytes += min(r.start, cap) * per_pos          # the ring before
        nbytes += r.n * per_pos                          # the chunk's k, v
        nbytes += 2 * r.n * model.heads * model.hd * BF16   # q in, out
    if flops:
        t.add(flops, nbytes, peaks)


def dispatch(model: Model, steps: Sequence[Sequence[Row]],
             peaks: Dict) -> Dict[str, Tally]:
    """Work of one dispatch, given its live rows at each of its steps (a
    prefill dispatch is one step whose rows carry several tokens; a decode
    dispatch is one step per token generated)."""
    out = {"ternary_matmul": Tally(), "chunk_attention": Tally(),
           "other": Tally()}
    for rows in steps:
        rows = [r for r in rows if r.n > 0]
        if not rows:
            continue
        m = sum(r.n for r in rows)
        m_head = sum(r.logits for r in rows)
        for _ in range(model.n_layers):
            for d_in, d_out in model.matrices():
                _ternary(model, out["ternary_matmul"], m, d_in, d_out, peaks)
            if model.kind == "qwen2":
                _attention(model, out["chunk_attention"], rows, peaks)
            out["other"].add(
                m * model.dense_flops_per_token(),
                model.dense_param_bytes()
                + 2 * len(rows) * model.state_bytes_per_row(), peaks)
        _ternary(model, out["ternary_matmul"], m_head, model.d, model.vocab,
                 peaks)
        out["other"].add(0.0, m * model.d * BF16, peaks)   # embedding rows
    return out


def total(tallies: Dict[str, Tally]) -> Tally:
    t = Tally()
    for v in tallies.values():
        t.merge(v)
    return t
