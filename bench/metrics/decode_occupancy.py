"""Share of decode-wave rows that carried a decoding request, from the
loop's counters over the window: decode_tokens / (decode_steps x batch)."""


def read(run):
    steps = sum(rd.stats.get("decode_steps", 0) for rd in run.rounds)
    toks = sum(rd.stats.get("decode_tokens", 0) for rd in run.rounds)
    if not steps:
        return None
    return 100.0 * toks / (steps * run.mix["serving"]["batch"])
