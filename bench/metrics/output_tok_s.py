"""Generated tokens resolved in the window over the window's wall time
(first timed round's submission to the end of the last round)."""


def read(run):
    tokens = sum(len(r.generated) for r in run.requests())
    return tokens / run.window_s
