"""Print what a JAX profiler trace holds: planes, lines, and the busiest
event names with a sample of their stats.

    python3 bench/tools/look_at_trace.py <trace dir or .xplane.pb> [--events N]

Use it once by hand on a new device or a new kernel before writing a
reduction against the trace: it shows which planes are devices, which
are host threads, and how the kernels and programs are named.
"""

from __future__ import annotations

import argparse
import collections
import sys
from pathlib import Path


def describe(path: Path, n_events: int = 25) -> str:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    out = [f"trace {path}"]
    for plane in data.planes:
        lines = list(plane.lines)
        out.append(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            out.append(f"  line {line.name!r}: {len(events)} events")
            total = collections.Counter()
            count = collections.Counter()
            sample = {}
            for ev in events:
                total[ev.name] += ev.duration_ns
                count[ev.name] += 1
                sample.setdefault(ev.name, ev)
            for name, ns in total.most_common(n_events):
                ev = sample[name]
                stats = {k: (str(v)[:160]) for k, v in list(ev.stats)[:8]}
                out.append(f"    {ns / 1e6:10.3f} ms x{count[name]:<5d} "
                           f"{name[:120]!r} start_ns={ev.start_ns} "
                           f"stats={stats}")
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path", type=Path)
    ap.add_argument("--events", type=int, default=25,
                    help="event names to list per line, busiest first")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from harness.trace import newest_xplane

    path = args.path if args.path.is_file() else newest_xplane(args.path)
    print(describe(path, args.events))
    return 0


if __name__ == "__main__":
    sys.exit(main())
