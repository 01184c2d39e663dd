"""System-level tests: sharding rules, dry-run subprocess, end-to-end story.

The full 89-cell dry-run matrix is exercised by ``repro.launch.sweep`` (results
in benchmarks/results/dryrun/); here we gate-check one representative cell per
mesh in a subprocess (the 512-device XLA flag must not leak into this process).
"""

import json
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import configs
from repro.launch import specs as specs_mod

REPO = Path(__file__).resolve().parents[1]


class TestShardingRules:
    """PartitionSpec derivation on an abstract 16×16 mesh (no devices)."""

    def _mesh(self, multi=False):
        from jax.sharding import AbstractMesh

        if multi:
            return AbstractMesh((2, 16, 16), ("pod", "data", "model"))
        return AbstractMesh((16, 16), ("data", "model"))

    @pytest.mark.parametrize("arch", list(configs.ARCH_IDS))
    @pytest.mark.parametrize("multi", [False, True])
    def test_param_specs_cover_tree_and_divide(self, arch, multi):
        from jax.sharding import PartitionSpec as P

        from repro.sharding import partition as part

        cfg = configs.get_config(arch)
        mesh = self._mesh(multi)
        shapes = specs_mod.params_specs(cfg)
        pspecs = part.param_pspecs(shapes, mesh)

        leaves_s = jax.tree.leaves(shapes)
        leaves_p = jax.tree.leaves(
            pspecs, is_leaf=lambda x: isinstance(x, P))
        assert len(leaves_s) == len(leaves_p)
        for sds, spec in zip(leaves_s, leaves_p):
            assert isinstance(spec, P)
            assert len(spec) <= len(sds.shape)
            for dim, ax in zip(sds.shape, tuple(spec)):
                if ax is None:
                    continue
                size = (mesh.shape[ax] if isinstance(ax, str)
                        else int(np.prod([mesh.shape[a] for a in ax])))
                assert dim % size == 0, (arch, sds.shape, spec)

    def test_large_params_are_actually_sharded(self):
        """llama3-405b must not replicate any O(d²) matrix — FSDP/TP must
        split every big kernel or it cannot fit 256 chips."""
        from jax.sharding import PartitionSpec as P

        from repro.sharding import partition as part

        cfg = configs.get_config("llama3-405b")
        mesh = self._mesh()
        shapes = specs_mod.params_specs(cfg)
        pspecs = part.param_pspecs(shapes, mesh)
        flat_s = jax.tree.leaves(shapes)
        flat_p = jax.tree.leaves(pspecs, is_leaf=lambda x: isinstance(x, P))
        for sds, spec in zip(flat_s, flat_p):
            n = int(np.prod(sds.shape))
            if n >= 16 * 1024 * 1024:  # any 16M+ param tensor
                assert any(ax is not None for ax in tuple(spec)), (
                    sds.shape, spec)

    @pytest.mark.parametrize("arch", ["grok-1-314b", "deepseek-moe-16b"])
    def test_quantized_expert_specs(self, arch):
        """Quantized MoE experts: plane/scale specs must exist and divide."""
        from jax.sharding import PartitionSpec as P

        from repro.sharding import partition as part

        cfg = configs.get_config(arch)
        mesh = self._mesh()
        qshapes = specs_mod.quantized_params_specs(cfg)
        pspecs = part.param_pspecs(qshapes, mesh)
        flat_s = jax.tree.leaves(qshapes)
        flat_p = jax.tree.leaves(pspecs, is_leaf=lambda x: isinstance(x, P))
        assert len(flat_s) == len(flat_p)
        for sds, spec in zip(flat_s, flat_p):
            for dim, ax in zip(sds.shape, tuple(spec)):
                if ax is None:
                    continue
                size = (mesh.shape[ax] if isinstance(ax, str)
                        else int(np.prod([mesh.shape[a] for a in ax])))
                assert dim % size == 0, (arch, sds.shape, spec)


class TestInputSpecs:
    @pytest.mark.parametrize("arch", list(configs.ARCH_IDS))
    def test_all_shapes_have_specs(self, arch):
        cfg = configs.get_config(arch)
        for shape in ("train_4k", "prefill_32k"):
            b = specs_mod.batch_specs(cfg, shape)
            assert all(isinstance(v, jax.ShapeDtypeStruct) for v in b.values())
        st, tok = specs_mod.decode_state_specs(cfg, "decode_32k")
        assert tok.shape[0] == 128
        leaves = jax.tree.leaves(st)
        assert leaves and all(isinstance(v, jax.ShapeDtypeStruct)
                              for v in leaves)


@pytest.mark.slow
class TestDryRunSubprocess:
    @pytest.mark.parametrize("mesh", ["single", "multi"])
    def test_representative_cell_compiles(self, mesh, tmp_path):
        proc = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun",
             "--arch", "qwen2-1.5b", "--shape", "train_4k",
             "--mesh", mesh, "--out", str(tmp_path)],
            cwd=str(REPO), capture_output=True, text=True, timeout=900,
            env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin",
                 "HOME": "/root"},
        )
        assert proc.returncode == 0, proc.stderr[-3000:]
        out = json.loads(
            (tmp_path / f"qwen2-1.5b__train_4k__{mesh}.json").read_text())
        assert out["n_chips"] == (512 if mesh == "multi" else 256)
        assert out["cost_analysis"]["flops"] > 0
        assert out["roofline"]["dominant"] in ("compute", "memory",
                                               "collective")


def test_dryrun_results_complete():
    """The committed dry-run cache must cover every runnable cell × mesh
    (36 × 2) plus the PTQTP-quantized inference variants (23)."""
    d = REPO / "benchmarks" / "results" / "dryrun"
    if not d.exists():
        pytest.skip("dry-run cache not generated yet")
    have = {p.stem for p in d.glob("*.json")}
    missing = []
    for arch, shape in configs.runnable_cells():
        for mesh in ("single", "multi"):
            if f"{arch}__{shape}__{mesh}" not in have:
                missing.append(f"{arch}__{shape}__{mesh}")
    assert not missing, missing
