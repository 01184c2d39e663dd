"""Reduce a JAX profiler trace to device busy time, idle gaps and kernel
time by name.

The benchmark writes two host annotations into the trace, ``MARK_OPEN``
and ``MARK_CLOSE``, and notes ``time.perf_counter`` inside each. Their
start times put the traced window on the trace's clock, and the offset
between the two clocks lets the engine's own spans (perf_counter seconds)
name what the host was doing in each idle gap of the device.

* busy: the union of the intervals in which an operation of the device's
  ``XLA Ops`` line ran, clipped to the window, averaged over the chips;
* kernel time: the summed durations of the ops that ``kernel_of`` names;
* ops: time per operation, keyed by ``op_key`` (the kernel's name, or the
  HLO instruction without its number and with its result type), leaving
  out the loops and calls that hold other ops;
* gaps: the window's complement of busy, on the first chip.

Each op event's name is its HLO text (``%name.12 = type opcode(...)``).
A kernel is found by marks, substrings of that text: the ternary matmul,
which every architecture runs, by its function's name (``CORE``), and
the kernels an architecture adds by the marks its module lists
(``KERNELS``). A kernel whose ``pallas_call`` shows in the trace under
the name of the call around it can be found by its signature instead:
the architecture module's ``unnamed_kernel(result type, operands)`` names
a TPU custom call that no mark found. A trace is read for the kernel set
of its cell's architecture (``kernel_set``); without one, for every
architecture beside this harness (``every_kernel``).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import re
import sys
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence, Tuple

MARK_OPEN = "bench.trace_window.open"
MARK_CLOSE = "bench.trace_window.close"
OPS_LINE = "XLA Ops"
# kernel -> substrings of its op names in the device trace: the kernel that
# every architecture runs
CORE = {"ternary_matmul": ("ternary",)}
# "%name.3 = <type>{layout} opcode(": a type is an array type or a tuple of
# them, whose layouts hold one level of parentheses
_HEAD = re.compile(r"^%?([^\s=]+) = (\((?:[^()]|\([^()]*\))*\)|\S+?)"
                   r"(?:\{[^}]*\})? ([a-z][\w-]*)\(")
_CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass(frozen=True)
class Kernels:
    """The kernels a trace is read for: kernel -> marks, and the
    architectures' ``unnamed_kernel`` fallbacks."""
    marks: Dict[str, Tuple[str, ...]]
    unnamed: Tuple[Callable[[str, int], Optional[str]], ...] = ()


def kernel_set(archs) -> Kernels:
    """``CORE`` and the kernels of the architecture modules ``archs``; an
    architecture that names a kernel of ``CORE`` is an error."""
    marks = {k: tuple(v) for k, v in CORE.items()}
    unnamed = []
    for arch in archs:
        for kernel, own in getattr(arch, "KERNELS", {}).items():
            if kernel in CORE:
                raise ValueError(f"{arch.__name__} redefines kernel "
                                 f"{kernel!r}")
            marks[kernel] = tuple(dict.fromkeys(marks.get(kernel, ())
                                                + tuple(own)))
        if hasattr(arch, "unnamed_kernel"):
            unnamed.append(arch.unnamed_kernel)
    return Kernels(marks, tuple(unnamed))


@functools.lru_cache(maxsize=1)
def every_kernel() -> Kernels:
    """The kernels of every architecture module beside this harness
    (``bench/configs/arch_*.py``), for a trace read for no one cell."""
    bench = str(Path(__file__).resolve().parents[1])
    if bench not in sys.path:       # the modules import the harness by name
        sys.path.insert(0, bench)
    from harness import spec

    return kernel_set(spec.every_arch(Path(bench)))


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                        # mean over chips
    ops: Dict[str, float]                # op_key -> seconds (all chips)
    kernel_s: Dict[str, float]           # kernel -> seconds (all chips)
    gaps: List[Tuple[float, float]]      # idle (start, end), trace ns
    offset_ns: Optional[float]           # trace ns - perf_counter ns
    n_chips: int


def _union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_and_gaps(intervals, lo: float, hi: float):
    """Busy seconds of ``intervals`` inside [lo, hi] and the idle gaps."""
    clipped = [(max(a, lo), min(b, hi)) for a, b in intervals
               if b > lo and a < hi]
    merged = _union(clipped)
    busy = sum(b - a for a, b in merged)
    gaps, t = [], lo
    for a, b in merged:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return busy, gaps


def _head(op_name: str):
    """(instruction name, result type, opcode) of an HLO op's text, or
    None where the text is not an instruction."""
    m = _HEAD.match(op_name)
    return m.groups() if m else None


def _operands(op_name: str) -> int:
    """Operands of a custom call: the ``%`` names in its argument list."""
    args = op_name.split("custom-call(", 1)[-1].split("), custom_call_target",
                                                       1)[0]
    return args.count("%")


def kernel_of(op_name: str, kernels: Optional[Kernels] = None
              ) -> Optional[str]:
    kernels = kernels or every_kernel()
    low = op_name.lower()
    for kernel, marks in kernels.marks.items():
        if any(m in low for m in marks):
            return kernel
    head = _head(op_name)
    if (head and head[2] == "custom-call"
            and 'custom_call_target="tpu_custom_call"' in op_name):
        for unnamed in kernels.unnamed:
            kernel = unnamed(head[1], _operands(op_name))
            if kernel is not None:
                return kernel
    return None


def op_key(op_name: str, kernels: Optional[Kernels] = None
           ) -> Optional[str]:
    """A short name that groups one kind of op: the kernel's name, or the
    instruction's name without its numbers, with its result type. None
    for an op that holds others (a loop or a call), whose time its body's
    ops already count."""
    kernel = kernel_of(op_name, kernels)
    if kernel is not None:
        return kernel
    head = _head(op_name)
    if head is None:
        return op_name[:80]
    name, result, opcode = head
    if opcode in _CONTAINERS:
        return None
    base = re.sub(r"(\.(\d+|clone))+$", "", name)
    return f"{base} {result}"


def newest_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce(xplane: Path, pc_open: float, pc_close: float,
           kernels: Optional[Kernels] = None) -> Reduction:
    """``pc_open``/``pc_close``: perf_counter read inside the two marks;
    ``kernels``: the cell's (``kernel_set``), or every architecture's."""
    from jax.profiler import ProfileData

    kernels = kernels or every_kernel()
    data = ProfileData.from_file(str(xplane))
    marks: Dict[str, float] = {}
    devices = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in (MARK_OPEN, MARK_CLOSE):
                        marks[ev.name] = ev.start_ns
        elif plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.append([(ev.name, ev.start_ns, ev.duration_ns)
                                    for ev in line.events])
    devices = [d for d in devices if d]
    if MARK_OPEN in marks and MARK_CLOSE in marks:
        lo, hi = marks[MARK_OPEN], marks[MARK_CLOSE]
        offset = lo - pc_open * 1e9          # trace ns - perf_counter ns
    else:
        ends = [(s, s + n) for d in devices for _, s, n in d]
        lo = min((a for a, _ in ends), default=0.0)
        hi = max((b for _, b in ends), default=0.0)
        offset = None
    ops: Dict[str, float] = collections.Counter()
    kernel_s: Dict[str, float] = collections.Counter()
    busy_total, gaps = 0.0, []
    for i, events in enumerate(devices):
        busy, g = busy_and_gaps([(s, s + n) for _, s, n in events], lo, hi)
        busy_total += busy
        if i == 0:
            gaps = g
        for name, s, n in events:
            inside = max(0.0, min(s + n, hi) - max(s, lo))
            if inside <= 0:
                continue
            key = op_key(name, kernels)
            if key is not None:
                ops[key] += inside / 1e9
            k = kernel_of(name, kernels)
            if k is not None:
                kernel_s[k] += inside / 1e9
    n = max(len(devices), 1)
    return Reduction(
        window_s=(hi - lo) / 1e9, busy_s=busy_total / n / 1e9,
        ops=dict(ops), kernel_s=dict(kernel_s), gaps=gaps, offset_ns=offset,
        n_chips=len(devices))


def name_gaps(red: Reduction, spans, top: int = 10) -> List[List]:
    """The ``top`` longest idle gaps as [what the host was doing,
    seconds]: the innermost engine span covering the gap's middle,
    "between engine steps" where no span covers it, "unattributed" where
    the clocks could not be aligned. ``spans``: (name, t0, t1) on the
    engine's track, perf_counter seconds."""
    out = []
    for a, b in sorted(red.gaps, key=lambda g: g[0] - g[1])[:top]:
        secs = (b - a) / 1e9
        if red.offset_ns is None:
            out.append(["unattributed", secs])
            continue
        mid = ((a + b) / 2 - red.offset_ns) / 1e9
        cover = [(t1 - t0, name) for name, t0, t1 in spans if t0 <= mid <= t1]
        out.append([min(cover)[1] if cover else "between engine steps", secs])
    return out
