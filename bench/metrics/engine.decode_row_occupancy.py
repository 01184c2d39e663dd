"""Tokens the decode loop generated in the window over the rows its
steps computed: (tokens generated - first tokens, which prefill samples)
/ (decode steps x max_slots), from the engine's counters."""


def read(run):
    o, c = run.counters["open"], run.counters["close"]
    steps = c["decode_steps"] - o["decode_steps"]
    firsts = sum(1 for r in run.records for t in r.times[:1]
                 if run.t_open <= t < run.t_close)
    if steps <= 0:
        return None
    tokens = c["tokens_generated"] - o["tokens_generated"] - firsts
    return tokens / (steps * run.max_slots)
