"""Split the device's idle time in a traced window by what the engine's
thread was doing.

``trace.reduce`` gives the window's idle gaps (trace ns) and the offset
between the trace's clock and the engine's (``offset_ns``). Each span the
engine recorded on its own track is moved to trace ns, and every gap is
split, instant by instant, by the innermost span over it (the shortest of
those that cover it): a sweep over the spans' edges, not the gap's middle.

Layers, by the innermost span's name:

* ``driver``: a ``driver_*`` span other than ``driver_idle``, the driver
  loop around the engine's steps (its lock, calls, offers, token fan-out);
* ``engine``: ``step``, a phase in the program's ``PHASES`` (the driver's
  aside) or ``compile``;
* ``driver_idle``: the driver parked with no work;
* ``unattributed``: no span, or one of another name.

The four add up to the gaps' length, device idle share × window.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from repro.serving.observability import PHASES

LAYERS = ("driver", "engine", "driver_idle", "unattributed")
ENGINE_TRACK = ("engine", 0)


def layer_of(name: Optional[str]) -> str:
    if name == "driver_idle":
        return "driver_idle"
    if name is not None and name.startswith("driver_"):
        return "driver"
    if name in ("step", "compile") or name in PHASES:
        return "engine"
    return "unattributed"


def _segments(spans: Sequence[Tuple[float, float, str]]):
    """Consecutive (lo, hi, name) pieces of time, each under one innermost
    span; time under no span has no piece."""
    edges = sorted({a for a, _, _ in spans} | {b for _, b, _ in spans})
    order = sorted(spans)
    active: List[Tuple[float, float, str]] = []
    out = []
    j = 0
    for k in range(len(edges) - 1):
        e = edges[k]
        while j < len(order) and order[j][0] <= e:
            active.append(order[j])
            j += 1
        active = [s for s in active if s[1] > e]
        if active:
            inner = min(active, key=lambda s: s[1] - s[0])
            out.append((e, edges[k + 1], inner[2]))
    return out


def split(gaps: Iterable[Tuple[float, float]], offset_ns: Optional[float],
          spans: Iterable[Tuple[str, float, float]]
          ) -> Optional[Dict[str, float]]:
    """Idle seconds per layer. ``gaps``: (start, end) in trace ns;
    ``spans``: (name, t0, t1) on the engine's track, in the engine clock's
    seconds. Nothing when the clocks could not be aligned."""
    if offset_ns is None:
        return None
    moved = [(t0 * 1e9 + offset_ns, t1 * 1e9 + offset_ns, name)
             for name, t0, t1 in spans if t1 > t0]
    segs = _segments(moved)
    ns = dict.fromkeys(LAYERS, 0.0)
    i = 0
    for lo, hi in sorted(gaps):
        while i < len(segs) and segs[i][1] <= lo:
            i += 1
        covered = 0.0
        k = i
        while k < len(segs) and segs[k][0] < hi:
            a, b = max(lo, segs[k][0]), min(hi, segs[k][1])
            if b > a:
                ns[layer_of(segs[k][2])] += b - a
                covered += b - a
            k += 1
        ns["unattributed"] += (hi - lo) - covered
    return {k: v / 1e9 for k, v in ns.items()}


def shares(run) -> Optional[Dict[str, float]]:
    """Each layer's idle seconds over the traced window; nothing without a
    trace, without aligned clocks, or without the driver loop's spans (a
    program that records none cannot be split this way)."""
    red = run.reduction
    if red is None or red.offset_ns is None or red.window_s <= 0:
        return None
    spans = [(e.name, e.ts, e.ts + e.dur) for e in run.spans
             if e.track == ENGINE_TRACK and e.ph == "X"]
    if not any(name == "driver_loop" for name, _, _ in spans):
        return None
    secs = split(red.gaps, red.offset_ns, spans)
    return {k: v / red.window_s for k, v in secs.items()}
