"""Attention kernels' share of their roofline over the traced rounds.

The least time of the pattern-live attention work the traced requests
needed (each prefill chunk call on its own, the decode positions
together), max(FLOPs / peak, bytes / bandwidth) from ``counts``, over the
summed device time of the paged attention kernels' events."""

import counts
import tracing

KERNELS = ("mha_chunk_paged", "mha_decode_paged")


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    lo, hi = run.trace.window()
    events = next(iter(run.trace.devices.values()))
    ns, n = tracing.match_ns(events, KERNELS, lo, hi)
    if not n or ns <= 0:
        return None
    peak, bw = run.peaks["bf16_flops_per_s"], run.peaks["hbm_bytes_per_s"]
    chunk = run.mix["serving"]["chunk"]
    least = dec_f = dec_b = 0.0
    for r in run.requests(traced_only=True):
        calls, (f, b) = counts.request_attention(
            run.shape, len(r.prompt), r.max_new, chunk)
        least += sum(max(cf / peak, cb / bw) for cf, cb in calls)
        dec_f += f
        dec_b += b
    least += max(dec_f / peak, dec_b / bw)
    return 100.0 * least / (ns * 1e-9)
