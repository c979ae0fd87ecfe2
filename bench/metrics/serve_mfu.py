"""Model FLOP utilization of the traced rounds: the model FLOPs of every
token they served (``counts.model_flops``: linears, pattern-live attention,
the LM head once per generated token; padding and recomputation excluded)
over traced window x chips x bf16 peak."""

import counts


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace.window()
    chunk = run.mix["serving"]["chunk"]
    flops = sum(counts.model_flops(run.shape, len(r.prompt), r.max_new, chunk)
                for r in run.requests(traced_only=True))
    return 100.0 * flops / ((hi - lo) * 1e-9 * run.chips
                            * run.peaks["bf16_flops_per_s"])
