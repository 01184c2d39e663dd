"""The weights made from the seed stay what they were: the tiny trees'
program leaves and reference leaves hash to the digests that the harness
gave before the leaf rules moved into the architecture modules."""

from __future__ import annotations

import hashlib

import numpy as np
import pytest

import bench_tiny_tree as tiny

from harness import model, weights

SEED = 2 ** 31 + 12345
# sha256 over (path, dtype, shape, bytes) of every leaf in order, from the
# harness of commit b567248, before the architecture modules, at the tiny
# trees' sizes in float32
PINNED = {
    ("qwen2-1.5b", "program"):
        "773e1d894355824eb247e06cf8893c44fa26b083682c7c249c8507f3eebce573",
    ("qwen2-1.5b", "reference"):
        "581cdad4f6e096c53230aa6303d1fd44fd124ee94222743c05589aabffbeb02a",
    ("rwkv6-3b", "program"):
        "f201fbf8eab2f75da0f6fb05c566dc864d415f5f6b3b0a2f03f894989164dd2c",
    ("rwkv6-3b", "reference"):
        "0246597176a3b4d09bc14c3a4ec73c6f0638ca7c4083f26249b636816e2e085e",
}


def _digest(named):
    d = hashlib.sha256()
    for path, a in named:
        a = np.asarray(a)
        d.update(f"{path}|{a.dtype}|{a.shape}|".encode())
        d.update(np.ascontiguousarray(a).tobytes())
    return d.hexdigest()


def program_leaves(c, arch):
    """(path, array) of every leaf of the program's params, in order."""
    import jax

    mcfg = model.model_config(c, arch)
    params = weights.program_params(mcfg, SEED,
                                    c["quantization"]["group_size"],
                                    weights.leaf_rules(arch))
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    return [(jax.tree_util.keystr(p), x) for p, x in flat]


def reference_leaves(c, arch):
    """(path@layer, array) of every leaf as the reference draws it, one
    layer of a stacked leaf at a time."""
    g = c["quantization"]["group_size"]
    w = weights.Seeded(SEED, weights.leaf_rules(arch))
    out = []
    for path, sds, ternary in weights.leaves(model.model_config(c, arch), g):
        shape = tuple(sds.shape)
        per = shape[1:] if weights.stacked(path) else shape
        for layer in (range(shape[0]) if weights.stacked(path) else [-1]):
            out.append((f"{path}@{layer}",
                        w.matrix(path, layer, per[-2], per[-1], g) if ternary
                        else w.leaf(path, layer, per, sds.dtype)))
    return out


@pytest.mark.parametrize("name,side", sorted(PINNED))
def test_seeded_leaves_are_unchanged(name, side, tmp_path):
    bench = tiny.tiny_tree(tmp_path)
    c = tiny.config(name, bench)
    draw = program_leaves if side == "program" else reference_leaves
    assert _digest(draw(c, tiny.arch(c, bench))) == PINNED[name, side]


def test_a_rule_may_not_redefine_a_shared_one():
    arch = type("arch_x", (), {"LEAF_RULES": {"bias": None}})
    with pytest.raises(ValueError):
        weights.leaf_rules(arch)
    with pytest.raises(KeyError):
        weights.dense_value(None, "/blocks/b0/x/unknown", (2,), "float32",
                            weights.SHARED_RULES)
