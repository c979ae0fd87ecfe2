"""Highest share of the page pool in use (requests' pages and the prefix
cache's), from the pool's own high-water counter after the window."""


def read(run):
    peak = run.rounds[-1].stats.get("pool_peak_pages")
    if peak is None:
        return None
    return 100.0 * peak / run.mix["serving"]["pool_pages"]
