"""Token-expert assignments routed over the rows of the expert tiles the
grouped kernel ran (padding included), summed over the prefill and decode
dispatches issued in the window, from the ``moe_routed_rows`` and
``moe_tile_rows`` args of their engine spans. Nothing where the program
reports no such args."""


def read(run):
    routed = tiles = 0
    for name in ("prefill_dispatch", "decode_dispatch"):
        for e in run.engine_spans(name, run.t_open, run.t_close):
            args = e.args or {}
            if "moe_tile_rows" in args:
                routed += args["moe_routed_rows"]
                tiles += args["moe_tile_rows"]
    return routed / tiles if tiles else None
