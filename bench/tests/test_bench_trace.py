"""The trace reduction: busy union, idle gaps named by the engine's spans,
and kernel time by name, on synthetic intervals and on a trace recorded
on a TPU v5e (``fixtures/``: the program's ternary matmul and chunk
attention kernels, traced between the benchmark's two window marks)."""

from __future__ import annotations

from pathlib import Path

import pytest

import bench_tiny_tree as tiny

from harness import trace

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "tpu_v5e.xplane.pb"


def test_busy_is_the_union_clipped_to_the_window():
    busy, gaps = trace.busy_and_gaps(
        [(0, 10), (5, 20), (30, 40), (38, 45), (90, 120)], 2, 100)
    assert busy == (20 - 2) + (45 - 30) + (100 - 90)
    assert gaps == [(20, 30), (45, 90)]


def test_gaps_are_named_by_the_innermost_engine_span():
    red = trace.Reduction(window_s=1.0, busy_s=0.5, ops={}, kernel_s={},
                          gaps=[(1e9, 1.1e9), (2e9, 2.5e9), (3e9, 3.2e9)],
                          offset_ns=0.0, n_chips=1)
    spans = [("step", 0.9, 1.2), ("decode_sync", 1.0, 1.15),
             ("step", 2.9, 3.3)]
    assert trace.name_gaps(red, spans) == [
        ["between engine steps", pytest.approx(0.5)],
        ["step", pytest.approx(0.2)],
        ["decode_sync", pytest.approx(0.1)]]
    red.offset_ns = None
    assert trace.name_gaps(red, spans)[0][0] == "unattributed"


# op texts as a TPU v5e trace of the chat cell spells them (operands cut)
TERNARY = ("%ternary_matmul_pallas.64 = f32[2048,1536]{1,0:T(8,128)S(1)} "
           "custom-call(bf16[4,2048,2240]{2,1,0:T(8,128)(2,1)} %m.2, "
           "u8[1536,2240]{1,0:T(8,128)(4,1)S(1)} %b.1, u8[1536,2240]{1,0} "
           "%b.2, f32[1536,140]{1,0} %c.3), "
           'custom_call_target="tpu_custom_call", frontend_attributes={}')
RING = ("%closed_call.13 = f32[64,2,192,128]{3,2,1,0:T(8,128)S(1)} "
        "custom-call(s32[64]{0} %g.1, bf16[64,2,192,128]{3,2,1,0} %q, "
        "bf16[64,32,256]{2,1,0} %k, bf16[64,32,256]{2,1,0} %v, "
        "bf16[64,3072,256]{2,1,0} %kc, f32[1,1,1]{2,1,0} %s, "
        "bf16[64,3072,256]{2,1,0} %vc, f32[1,1,1]{2,1,0} %s, "
        "s32[64,1,3072]{2,1,0} %pos, s32[64,192,1]{2,1,0} %qp, "
        "s32[64,1,32]{2,1,0:T(1,128)S(1)} %kp), "
        'custom_call_target="tpu_custom_call", frontend_attributes={}')
LOOP = ("%while.4 = (s32[]{:T(128)}, bf16[64,32,1536]{1,0,2:T(8,128)(2,1)}, "
        "/*index=2*/f32[28,256,12,2]{1,3,2,0:T(2,128)}) while((s32[]{:T(128)}"
        ", bf16[64,32,1536]{1,0,2:T(8,128)(2,1)}) %tuple.9), condition=%c")
FILL = ("%broadcast.451.clone.2 = bf16[28,64,3072,2,128]{4,3,2,1,0:T(2,128)"
        "(2,1)} broadcast(bf16[]{:T(256)} %constant.284), dimensions={}")


def _qwen2_kernels():
    return trace.kernel_set([tiny.arch(tiny.config("qwen2-1.5b"))])


def test_kernel_names():
    assert trace.kernel_of(RING, trace.kernel_set([])) is None   # core only
    assert trace.kernel_of(RING, _qwen2_kernels()) == "chunk_attention"
    assert trace.kernel_of("_ternary_kernel") == "ternary_matmul"
    assert trace.kernel_of(TERNARY) == "ternary_matmul"
    assert trace.kernel_of(RING) == "chunk_attention"
    assert trace.kernel_of(RING.replace("%kp)", "%kp, s32[1]{0} %x)")) \
        == "chunk_attention"                               # paged: 12
    assert trace.kernel_of(RING.replace(", s32[64,1,32]{2,1,0:T(1,128)S(1)}"
                                        " %kp", "")) is None
    assert trace.kernel_of("fusion.12") is None
    assert trace.kernel_of(FILL) is None


def test_op_keys_group_ops_and_leave_out_loops():
    assert trace.op_key(TERNARY) == "ternary_matmul"
    assert trace.op_key(RING) == "chunk_attention"
    assert trace.op_key(LOOP) is None
    assert trace.op_key(FILL) == "broadcast bf16[28,64,3072,2,128]"


def test_recorded_tpu_trace():
    """Busy time against a plain sweep over the same events, and both
    kernels found with positive time."""
    from jax.profiler import ProfileData

    red = trace.reduce(FIXTURE, 0.0, 0.0)
    assert red.n_chips == 1 and red.offset_ns is not None
    data = ProfileData.from_file(str(FIXTURE))
    marks = {ev.name: ev.start_ns for p in data.planes
             if p.name.startswith("/host:") for ln in p.lines
             for ev in ln.events
             if ev.name in (trace.MARK_OPEN, trace.MARK_CLOSE)}
    lo, hi = marks[trace.MARK_OPEN], marks[trace.MARK_CLOSE]
    edges = sorted((max(ev.start_ns, lo), min(ev.start_ns + ev.duration_ns,
                                              hi))
                   for p in data.planes if p.name.startswith("/device:")
                   for ln in p.lines if ln.name == trace.OPS_LINE
                   for ev in ln.events
                   if ev.start_ns + ev.duration_ns > lo and ev.start_ns < hi)
    busy, end = 0.0, lo
    for a, b in edges:
        if b > end:
            busy += b - max(a, end)
            end = b
    assert red.busy_s == pytest.approx(busy / 1e9)
    assert red.window_s == pytest.approx((hi - lo) / 1e9)
    assert 0 < red.busy_s < red.window_s
    assert red.kernel_s["ternary_matmul"] > 0
    assert red.kernel_s["chunk_attention"] > 0


def test_fixture_ops_keep_their_kernels():
    """Every op of the recorded trace keeps the op key and kernel that the
    harness of commit b567248 gave it, before the kernels moved into the
    architecture modules: 42 op keys (sha256 of their sorted JSON list,
    computed then), of which only the two kernels' own name a kernel."""
    import hashlib
    import json

    from jax.profiler import ProfileData

    kernels = _qwen2_kernels()
    found = {}
    for p in ProfileData.from_file(str(FIXTURE)).planes:
        if p.name.startswith("/device:"):
            for ln in p.lines:
                if ln.name == trace.OPS_LINE:
                    for ev in ln.events:
                        found.setdefault(str(trace.op_key(ev.name, kernels)),
                                         set()).add(
                            trace.kernel_of(ev.name, kernels))
    assert len(found) == 42
    assert hashlib.sha256(json.dumps(sorted(found)).encode()).hexdigest() \
        == "cd3748ae5c7cb5d3afb4574b5261fb64cc6cc8f9ea8ab7f27116436537b46015"
    assert {k: v for k, v in found.items() if v != {None}} == {
        "chunk_attention": {"chunk_attention"},
        "ternary_matmul": {"ternary_matmul"}}
    assert all(len(v) == 1 for v in found.values())


@pytest.mark.parametrize("kernel", ["chunk_attention", "ternary_matmul"])
def test_cell_kernel_set_reads_the_fixture(kernel):
    """The chat cell's kernel set finds both kernels' time in the recorded
    trace, as every architecture's does."""
    ours = trace.reduce(FIXTURE, 0.0, 0.0, _qwen2_kernels())
    every = trace.reduce(FIXTURE, 0.0, 0.0)
    assert ours.kernel_s[kernel] == every.kernel_s[kernel] > 0
    assert ours.ops == every.ops
