"""Arithmetic that several per-layer readers share (one definition each)."""

from __future__ import annotations

from harness import work


def prefill_useful_share(run):
    """Prompt tokens prefilled / Σ(bucket × max_slots) over the prefill
    dispatches issued in the window."""
    dispatches = run.engine_spans("prefill_dispatch", run.t_open, run.t_close)
    rows = sum(e.args["bucket"] for e in dispatches) * run.max_slots
    if not rows:
        return None
    uids = {r.uid for r in run.records}
    tokens = sum(e.args["tokens"] for e in run.spans
                 if e.name == "prefill_chunk" and e.track[1] in uids
                 and run.t_open <= e.ts <= run.t_close)
    return tokens / rows


def step_mfu(run):
    flops = work.total(run.work).flops
    busy = run.reduction.busy_s
    if not flops or busy <= 0:
        return None
    return 100.0 * flops / (busy * run.peaks["bf16_flops_per_s"])


def step_hbm_share(run):
    nbytes = work.total(run.work).bytes
    busy = run.reduction.busy_s
    if not nbytes or busy <= 0:
        return None
    return nbytes / (busy * run.peaks["hbm_bytes_per_s"])


def roofline(run, kernel: str):
    """Σ roofline time of the kernel's needed work / Σ its kernel time, in
    %; nothing when the trace holds no event of it."""
    t = run.reduction.kernel_s.get(kernel, 0.0)
    need = run.work[kernel].roofline_s
    if t <= 0 or need <= 0:
        return None
    return 100.0 * need / t


def idle_share(run):
    red = run.reduction
    if red.window_s <= 0:
        return None
    return 1.0 - red.busy_s / red.window_s
