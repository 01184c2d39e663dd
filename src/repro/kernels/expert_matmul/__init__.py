"""Grouped ternary matmul of the routed experts: rows sorted by expert in
tiles, each tile multiplied by its own expert's trit-planes, tiles past
the live count skipped (``kernel.py``; ``ops.expert_matmul``)."""

from repro.kernels.expert_matmul.ops import TILE_ROWS, expert_matmul, n_tiles

__all__ = ["TILE_ROWS", "expert_matmul", "n_tiles"]
