"""The tiny tree (``bench_tiny_tree``) spells out test-size traffic for the
mixes it was written with; a mix added since runs, at test size, as the
tiny stand-in of its own kind."""

import json

import bench_tiny_tree as tiny

for _w in tiny.top()["workloads"]:
    if _w["traffic"] not in tiny.TRAFFIC:
        _kind = json.loads((tiny.BENCH / "traffic" / f"{_w['traffic']}.json")
                           .read_text())["kind"]
        tiny.TRAFFIC[_w["traffic"]] = next(
            t for t in tiny.TRAFFIC.values() if t["kind"] == _kind)
