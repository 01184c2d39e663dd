"""Resolve a cell of ``BENCHMARK.json`` into the files that define it.

Each is found by name: the configuration file by ``BENCHMARK.json``'s
entry, its plain reference by the file's ``reference``, its architecture
module (``configs/arch_<model_type>.py``) by the file's ``model_type``,
the traffic mix by the cell's ``traffic``, and each per-layer metric's
reader by the metric's name.

An architecture module gives what the harness counts per architecture:

* ``model_config(c)``: the program's ``ModelConfig`` keywords for
  configuration file ``c`` (``model.model_config`` adds the shared ones);
* ``layer(model, i)``: the work of layer ``i`` (``work.Layer``);
* ``KERNELS`` (optional): kernel -> trace marks of the kernels it adds to
  the ternary matmul, and ``unnamed_kernel(result, operands)`` (optional)
  for one that shows in a trace under another call's name (``trace``);
* ``LEAF_RULES`` (optional): name -> rule of the non-ternary leaves it
  alone has (``weights.leaf_rules``);
* ``SHRINK``: the keys that cut a configuration of this type to test
  size (the tests' tiny trees).
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parents[1]


class SpecError(Exception):
    """The benchmark's files do not define the cell asked for."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict[str, Any]       # bench/configs/<config>.json, as run
    arch: Any                    # bench/configs/arch_<model_type>.py
    traffic: Dict[str, Any]      # bench/traffic/<traffic>.json
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    bench_dir: Path

    def reference(self):
        """The configuration's plain reference module (named by the config
        file, kept beside it)."""
        return load_module(self.bench_dir / "configs" / self.config["reference"])

    def reader(self, metric: str) -> Callable:
        """``read(run)`` of a per-layer metric (bench/metrics/<name>.py)."""
        return load_module(self.bench_dir / "metrics" / f"{metric}.py").read


def load_module(path: Path):
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ARCH_NAMES = ("model_config", "layer", "SHRINK")


def load_arch(bench_dir: Path, model_type: str):
    """The architecture module of ``model_type``, beside the
    configurations."""
    mod = load_module(Path(bench_dir) / "configs" / f"arch_{model_type}.py")
    missing = [n for n in ARCH_NAMES if not hasattr(mod, n)]
    if missing:
        raise SpecError(f"architecture {model_type!r} lacks {missing}")
    return mod


def every_arch(bench_dir: Path = BENCH) -> List[Any]:
    """Every architecture module under ``bench_dir/configs``."""
    return [load_arch(bench_dir, p.stem[len("arch_"):]) for p in
            sorted((Path(bench_dir) / "configs").glob("arch_*.py"))]


def _read_json(path: Path) -> Dict[str, Any]:
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    return json.loads(path.read_text())


def _applies(entry: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(root: Path, name: str, bench_dir: Optional[Path] = None) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``; its configuration and
    traffic are found by name under ``bench_dir`` (default: this
    harness's own directory)."""
    bench_dir = Path(bench_dir) if bench_dir is not None else BENCH
    top = _read_json(Path(root) / "BENCHMARK.json")
    cells = {w["name"]: w for w in top["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in top["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names unknown config "
                        f"{w['config']!r}")
    config = _read_json(Path(root) / configs[w["config"]]["file"])
    from harness.correct import STATS

    if not any(k in config.get("correct", {}) for k in STATS):
        raise SpecError(f"config {w['config']!r} compares no gap statistic")
    arch = load_arch(bench_dir, config["model_type"])
    traffic = _read_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in top["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in top["per_layer"]
                 if _applies(m, name) and m["moves"] in reported]
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                traffic_name=w["traffic"], config=config, arch=arch,
                traffic=traffic,
                end_to_end=e2e, per_layer=per_layer, bench_dir=bench_dir)
