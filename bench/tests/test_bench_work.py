"""The work counters against hand-reckoned counts: only live rows, live
ring positions and stored weight bytes."""

from __future__ import annotations

import pytest

import bench_tiny_tree as tiny

from harness import device, work

PEAKS = device.peaks("TPU v5 lite")


def _model(name):
    c = tiny.config(name)
    return work.Model(c, tiny.arch(c))


def _stored(d_in, d_out):
    """Two 2-bit planes and f32 (α¹, α²) per group of 128 inputs."""
    return 2 * d_out * d_in // 4 + d_out * (d_in // 128) * 2 * 4


def test_qwen2_prefill_dispatch():
    """Two live rows in a 64 x 32 padded dispatch: row A prompt positions
    0..31 (not finished), row B positions 100..109 (finished, so it needs
    the output head). The 62 idle rows and 22 padded tokens of row B count
    nothing."""
    m = _model("qwen2-1.5b")
    out = work.dispatch(m, [[work.Row(0, 32, 0), work.Row(100, 10, 1)]],
                        PEAKS)
    d, hkv, ff, v, L = 1536, 256, 8960, 151936, 28
    mats = [(d, d), (d, hkv), (d, hkv), (d, d), (d, ff), (d, ff), (ff, d)]
    tok = 42
    flops = L * sum(2 * tok * i * o for i, o in mats) + 2 * 1 * d * v
    nbytes = (L * sum(_stored(i, o) + 2 * tok * (i + o) for i, o in mats)
              + _stored(d, v) + 2 * 1 * (d + v))
    assert out["ternary_matmul"].flops == flops == 110_523_187_200
    assert out["ternary_matmul"].bytes == nbytes
    assert out["ternary_matmul"].calls == 7 * L + 1
    # attention: A's queries see 1..32 keys (528), B's 101..110 (1055);
    # B reads its 100 ring positions, both write their chunk (1 KiB per
    # position per layer: k and v, 2 heads x 128 x bf16), q in and out
    keys = 528 + 1055
    assert out["chunk_attention"].flops == L * 4 * 12 * 128 * keys
    per_layer = (100 * 1024 + 42 * 1024 + 2 * 42 * 12 * 128 * 2)
    assert out["chunk_attention"].bytes == L * per_layer
    # norms and biases once per layer, two embedding rows gathered per token
    assert out["other"].bytes == L * 2 * (1536 + 512 + 2 * d) + 42 * d * 2


def test_rwkv6_decode_dispatch():
    """A decode dispatch of two steps: step 0 has live rows at positions
    200 and 50, step 1 only the first (the second finished). Every step
    reads the weights once and reads and writes each live row's state."""
    m = _model("rwkv6-3b")
    steps = [[work.Row(200, 1, 1), work.Row(50, 1, 1)], [work.Row(201, 1, 1)]]
    out = work.dispatch(m, steps, PEAKS)
    d, ff, v, L, h, hd = 2560, 8960, 65536, 32, 40, 64
    mats = [(d, d)] * 5 + [(d, ff), (ff, d), (d, d)]
    flops = sum(L * sum(2 * rows * i * o for i, o in mats) + 2 * rows * d * v
                for rows in (2, 1))
    assert out["ternary_matmul"].flops == flops == 17_364_418_560
    assert out["ternary_matmul"].bytes == sum(
        L * sum(_stored(i, o) + 2 * rows * (i + o) for i, o in mats)
        + _stored(d, v) + 2 * rows * (d + v) for rows in (2, 1))
    assert set(out) == {"ternary_matmul", "other"}   # no attention call
    lora = d * 160 + 160 * d + d * 64 + 64 * d
    per_token = 2 * lora + 7 * h * hd * hd
    assert out["other"].flops == L * per_token * 3
    params = 2 * (lora + 12 * d + h * hd)
    state = h * hd * hd * 4 + 2 * d * 2
    assert out["other"].bytes == (L * (2 * params + 2 * 3 * state)
                                  + 3 * d * 2)


def test_roofline_time_takes_each_calls_own_bound():
    t = work.Tally()
    t.add(197e12, 0.0, PEAKS)             # 1 s of compute
    t.add(0.0, 819e9, PEAKS)              # 1 s of bytes
    assert t.roofline_s == pytest.approx(2.0)
    assert t.compute_bound_s == pytest.approx(1.0)


@pytest.mark.parametrize("name", [c["name"] for c in tiny.top()["configs"]])
def test_work_counts_the_matrices_the_program_quantizes(name, tmp_path):
    """The ternary matrices the work model counts per layer, and the output
    head, are the leaves the program's own quantizer picks, at test size."""
    bench = tiny.tiny_tree(tmp_path)
    c = tiny.config(name, bench)
    arch = tiny.arch(c, bench)
    per_layer, rest = tiny.quantized_matrices(c, arch)
    assert per_layer == [sorted(m[:2] for m in layer.matrices)
                         for layer in work.Model(c, arch).layers]
    assert rest == [(c["hidden_size"], c["vocab_size"])]


# the work of dispatches other than the two above, as the harness of commit
# b567248 counted it before the architecture modules (flops, bytes,
# roofline s, compute-bound s, calls)
PINNED = {
    "qwen2-1.5b": (
        [[work.Row(3000, 1, 1), work.Row(7, 1, 1), work.Row(4000, 1, 1)],
         [work.Row(3001, 1, 1)]],
        {"ternary_matmul": [12348555264.0, 1746975744.0,
                            0.0021330595164835197, 0.0, 394],
         "chunk_attention": [1562566656.0, 261144576.0, 0.000318857846153846,
                             0.0, 56],
         "other": [0.0, 585728.0, 7.151746031746026e-07, 0.0, 58]}),
    "rwkv6-3b": (
        [[work.Row(0, 32, 0), work.Row(96, 17, 1)]],
        {"ternary_matmul": [267512709120.0, 1796641792.0,
                            0.0021937018217338135, 0.0, 257],
         "other": [5394923520.0, 160977920.0, 0.00019655423687423696, 0.0,
                   33]}),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_tallies_are_unchanged(name):
    steps, want = PINNED[name]
    out = work.dispatch(_model(name), steps, PEAKS)
    assert {k: [t.flops, t.bytes, t.roofline_s, t.compute_bound_s, t.calls]
            for k, t in out.items()} == want
