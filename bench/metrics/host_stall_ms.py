"""Longest single host self time of a loop phase other than the token
resolve (``stats["host_<phase>_max_s"]`` for step, admit, decode and
chunk) over the window's rounds, in milliseconds: the longest the host
held the loop in one phase.  Nothing to read from a program without the
counters."""

KEYS = tuple(f"host_{p}_max_s" for p in ("step", "admit", "decode", "chunk"))


def read(run):
    longest = [rd.stats[k] for rd in run.rounds for k in KEYS if k in rd.stats]
    if not longest:
        return None
    return 1e3 * max(longest)
