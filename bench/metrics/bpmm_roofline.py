"""BPMM linear kernels' share of their roofline over the traced rounds.

Work: every token the traced programs served (prompt tokens in chunk
calls, decoding rows in waves) through each layer's Monarch factors, and
each program's read of the factors (bf16), from ``counts``.  Its least
time, max(FLOPs / peak, bytes / bandwidth), over the summed device time of
the fused BPMM kernel's events."""

import counts
import tracing

KERNELS = ("monarch_bpmm",)


def read(run):
    if run.trace is None or not run.trace.devices or run.shape.linears != "bpmm":
        return None
    lo, hi = run.trace.window()
    events = next(iter(run.trace.devices.values()))
    ns, n = tracing.match_ns(events, KERNELS, lo, hi)
    if not n or ns <= 0:
        return None
    ms = run.shape
    traced = [rd for rd in run.rounds if rd.traced]
    programs = sum(rd.stats.get("chunk_calls", 0) + rd.stats.get("decode_steps", 0)
                   for rd in traced)
    tokens = sum(len(r.prompt) + r.max_new - 1 for rd in traced for r in rd.requests)
    flops = tokens * ms.layers * counts.linear_flops_per_token(ms)
    nbytes = ms.layers * (programs * counts.bpmm_weight_bytes(ms)
                          + tokens * counts.bpmm_io_bytes_per_token(ms))
    least = max(flops / run.peaks["bf16_flops_per_s"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ns * 1e-9)
