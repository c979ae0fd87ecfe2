"""Share of the window's ``ServeLoop.run()`` time in which the host was not
blocked waiting for sampled tokens: 100 x (1 - the loop's summed resolve
self time, ``stats["host_resolve_s"]``, / the rounds' summed run() time).
Nothing to read from a program without the counter."""


def read(run):
    waits = [rd.stats.get("host_resolve_s") for rd in run.rounds]
    if not waits or None in waits:
        return None
    wall = sum(rd.end - rd.submit for rd in run.rounds)
    return 100.0 * (1.0 - sum(waits) / wall)
