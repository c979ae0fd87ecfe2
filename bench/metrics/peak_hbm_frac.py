"""The fullest chip's peak bytes in use over its byte limit, read from the
runtime's memory counters after the window."""


def read(run):
    if not run.memory_limit or not run.memory_peak:
        return None
    return 100.0 * run.memory_peak / run.memory_limit
