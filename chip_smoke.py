#!/usr/bin/env python3
"""Smoke run of the paged serving path on TPU, at published widths.

    python3 chip_smoke.py [--seed 0]             # one chip
    python3 chip_smoke.py --chips 4 [--seed 0]   # the page-sharded path only

One chip: ``qwen3-0.6b`` (28 layers, d_model 1024, 16 query heads over 8 KV
heads of 128, vocab 151936) with random weights from ``--seed``, served by
``ServeLoop(paged=True, chunked=True)`` through the fused butterfly-sparse
attention kernels (batch 8, cache_len 4096, chunk 256).  Phases:

* ``bf16``  — 16 seeded requests: prompts of 300-3000 tokens, 16-32 new
  tokens each, arrivals mid-decode, 4 requests sharing a 1024-token prefix
  (the radix cache must hit).
* ``check`` — the first-chunk logits of one prompt (and of its following
  chunks) through the loop's paged chunk entry, fused kernel against the XLA
  form on the same params, at bf16, int8 and fp8_e4m3 pools: relative L2
  error within ``LOGIT_TOL``.
* ``int8``  — the bf16 phase's first requests on an int8 page pool.
* ``bpmm``  — ``qwen3-0.6b+bpmm-k+flash+butterfly_attn``: the fused BPMM
  linear kernel at its real factor shapes.

Each serving phase proves the Pallas kernels ran (``tpu_custom_call`` in the
compiled chunk and decode programs), checks every request produced its
tokens, and that the pools drain at ``close()``; it prints compile seconds,
wall seconds, tokens generated and the device's ``peak_bytes_in_use``.  These
are diagnostics, not a benchmark.

``--chips 4``: ``DisaggRouter`` over a 4-way page-sharded pool
(``make_pages_mesh(4)``, XLA attention — the fused kernel is one per device)
against the single-loop engine on one device (fused kernels), on the same
prompts: chunk logits within ``LOGIT_TOL``, pool shards on 4 distinct
devices each holding its own page range, both pools drained.

Every earlier line is a diagnostic; the last line of standard output is one
JSON object ``{"ok": true, "device": {...}}``.  Any failed check raises, and
the script then exits nonzero without that line.  It needs a TPU: on any
other platform it exits nonzero naming the platform it found.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

ARCH = "qwen3-0.6b"
BPMM_ARCH = "qwen3-0.6b+bpmm-k+flash+butterfly_attn"
BATCH, CACHE_LEN, CHUNK = 8, 4096, 256
PATTERN = "butterfly"
PREFIX = 1024  # shared-prefix length of the radix-cache requests
# fused kernel vs XLA form, both bf16 activations: relative L2 error of the
# logits, ||fused - xla|| / ||xla||, per chunk.  The two forms round the
# softmax differently (the XLA form casts probabilities to bf16) in each of 28
# layers; at 28 layers and reduced width the CPU interpreter measures up to
# 2.7e-2, while a wrong page, mask or scale moves the error by O(1)
LOGIT_TOL = 5e-2


def _require_tpu(n_chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(
            f"chip_smoke: needs a TPU, but JAX found platform "
            f"{devs[0].platform!r} ({devs[0].device_kind}); CPU runs are "
            "for the tests only"
        )
    if len(devs) < n_chips:
        sys.exit(f"chip_smoke: --chips {n_chips} needs {n_chips} TPU chips, "
                 f"found {len(devs)}")
    return devs


class CompileClock:
    """Seconds of XLA backend compilation, summed from JAX's monitoring
    events (a persistent-cache hit costs only its lookup).  Tracing is left
    out: its events nest, so their durations overlap."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.secs += duration


def workload(vocab: int, seed: int):
    """16 requests: 4 that share a PREFIX-token prefix (the first admitted
    at clock 0, the other three arriving once it has been cached) and 12 of
    300-3000 prompt tokens, 4 of them arriving mid-decode."""
    import numpy as np

    from repro.launch.serving import Request

    rng = np.random.default_rng(seed)
    shared = rng.integers(0, vocab, PREFIX).astype(np.int32)
    reqs = []
    for j in range(4):
        suffix = rng.integers(0, vocab, int(rng.integers(64, 512)))
        reqs.append(Request(
            uid=j, prompt=np.concatenate([shared, suffix.astype(np.int32)]),
            max_new=int(rng.integers(16, 33)),
            arrival=0 if j == 0 else 48 + 16 * j,
        ))
    for i in range(4, 16):
        plen = int(rng.integers(300, 3001))
        reqs.append(Request(
            uid=i, prompt=rng.integers(0, vocab, plen).astype(np.int32),
            max_new=int(rng.integers(16, 33)),
            arrival=0 if i < 11 else 12 * (i - 10),
        ))
    return reqs


def peak_bytes(device) -> int:
    return device.memory_stats()["peak_bytes_in_use"]


def _custom_calls(lowered) -> int:
    return lowered.compile().as_text().count('custom_call_target="tpu_custom_call"')


def kernel_count(loop, reqs) -> dict:
    """``tpu_custom_call`` ops in the compiled chunk and decode programs of
    the largest kv_live buckets the run used (cache hits: the run compiled
    them)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.serving.queueing import _next_bucket

    pools = loop._pools
    kv_chunk = _next_bucket(max(len(r.prompt) for r in reqs), loop.cache_len)
    kv_dec = loop.stats["decode_kv_live_max"]
    i32 = jnp.int32
    chunk = _custom_calls(loop.p_chunk_fn.jit_for(kv_chunk).lower(
        loop.params, pools, jnp.zeros((1, loop.chunk_size), i32),
        jnp.zeros((1, loop.n_vtiles), i32), i32(0), i32(1),
    ))
    dec = _custom_calls(loop.p_decode_fn.jit_for(kv_dec).lower(
        loop.params, pools, jnp.zeros((loop.batch, 1), i32),
        jnp.zeros((loop.batch,), i32),
        jnp.asarray(np.zeros((loop.batch, loop.n_vtiles), np.int32)),
    ))
    return {"chunk": chunk, "decode": dec, "kv_live": (kv_chunk, kv_dec)}


def serve_phase(name, cfg, mesh, params, reqs, clock, device, *,
                kv_dtype="bf16", attn_impl="flash_kernel", loop_cls=None,
                batch=BATCH, **kw):
    """Serve ``reqs`` through one engine; check and print the phase."""
    import jax

    from repro.launch.serving import ServeLoop

    loop_cls = loop_cls or ServeLoop
    c0, t0 = clock.secs, time.perf_counter()
    loop = loop_cls(
        cfg, mesh, params, batch=batch, cache_len=CACHE_LEN, paged=True,
        chunked=True, chunk_size=CHUNK, attn_impl=attn_impl,
        attn_pattern=PATTERN, kv_dtype=kv_dtype, **kw,
    )
    done = loop.run(reqs)
    jax.block_until_ready(loop._pools)
    wall = time.perf_counter() - t0
    compile_s = clock.secs - c0
    short = [r.uid for r in done if len(r.generated) != r.max_new]
    if short:
        raise RuntimeError(f"{name}: requests {short} did not finish")
    kernels = kernel_count(loop, reqs) if attn_impl == "flash_kernel" else None
    if kernels is not None and min(kernels["chunk"], kernels["decode"]) < 1:
        raise RuntimeError(f"{name}: no tpu_custom_call in {kernels}")
    loop.close()  # raises when a page is still referenced
    if loop.pool.in_use:
        raise RuntimeError(f"{name}: {loop.pool.in_use} pages in use after close()")
    st = loop.stats
    print(
        f"[{name}] {cfg.name} kv_dtype={kv_dtype} attn={attn_impl}/{PATTERN}: "
        f"{len(done)} requests, {sum(len(r.generated) for r in done)} tokens "
        f"generated, compile_s={compile_s:.1f} wall_s={wall:.1f} "
        f"peak_bytes_in_use={peak_bytes(device)} "
        f"tpu_custom_call={kernels} chunk_calls={st['chunk_calls']} "
        f"decode_steps={st['decode_steps']} prefix_hits={st['prefix_hits']} "
        f"pool_peak_pages={st['pool_peak_pages']}/{st['pool_pages']} "
        "pools drained",
        flush=True,
    )
    return loop, done


def chunk_logits(cfg, mesh, params, prompt, *, attn_impl, kv_dtype, n_pages):
    """Per-chunk logits of ``prompt`` streamed through a fresh pool by the
    paged chunk entry point the loop uses (:func:`make_paged_fns`)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.serving.entries import make_paged_fns, zero_pools
    from repro.launch.serving.queueing import _next_bucket

    page = 128
    _, _, chunk_fn, _, _ = make_paged_fns(
        cfg, mesh, n_pages=n_pages, page=page, chunk=CHUNK,
        attn_impl=attn_impl, attn_pattern=PATTERN, kv_dtype=kv_dtype,
    )
    pools = zero_pools(cfg, mesh, n_pages, page, kv_dtype=kv_dtype)
    pt = np.full((1, CACHE_LEN // page), n_pages, np.int32)
    n_tiles = -(-len(prompt) // page)
    pt[0, :n_tiles] = np.arange(n_tiles)
    out = []
    for start in range(0, len(prompt), CHUNK):
        t = min(CHUNK, len(prompt) - start)
        toks = np.zeros((1, CHUNK), np.int32)
        toks[0, :t] = prompt[start:start + t]
        logits, pools = chunk_fn(
            params, pools, jnp.asarray(toks), jnp.asarray(pt),
            jnp.int32(start), jnp.int32(t), _next_bucket(start + t, CACHE_LEN),
        )
        out.append(np.asarray(logits, np.float32))
    del pools
    return np.stack(out)


def compare_logits(name, test, ref):
    """Relative L2 error per chunk, gated at LOGIT_TOL; greedy (argmax)
    agreement is printed, not gated."""
    import numpy as np

    if not (np.all(np.isfinite(test)) and np.all(np.isfinite(ref))):
        raise RuntimeError(f"{name}: non-finite logits")
    rel = np.linalg.norm(test - ref, axis=-1) / np.linalg.norm(ref, axis=-1)
    agree = int(np.sum(test.argmax(-1) == ref.argmax(-1)))
    print(
        f"[{name}] logits {test.shape}: rel_l2 per chunk "
        f"{[float(x) for x in rel]} (tol {LOGIT_TOL}), max_abs "
        f"{float(np.max(np.abs(test - ref)))}, greedy agreement "
        f"{agree}/{len(rel)}",
        flush=True,
    )
    if not np.all(rel <= LOGIT_TOL):
        raise RuntimeError(f"{name}: logits rel_l2 {rel.max()} > {LOGIT_TOL}")


def _agreement(a, b) -> str:
    """Greedy tokens two engines agree on before each request's first
    divergence, over all tokens."""
    same = total = 0
    for ra, rb in zip(a, b):
        total += min(len(ra.generated), len(rb.generated))
        for x, y in zip(ra.generated, rb.generated):
            if x != y:
                break
            same += 1
    return f"{same}/{total}"


def _fresh(reqs):
    from repro.launch.serving import Request

    return [Request(uid=r.uid, prompt=r.prompt, max_new=r.max_new,
                    arrival=r.arrival) for r in reqs]


def run_one_chip(cfg, bpmm_cfg, mesh, seed, clock, device):
    """The one-chip phases; ``cfg`` / ``bpmm_cfg`` are the model configs."""
    import jax

    from repro.models import model as M

    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    reqs = workload(cfg.vocab, seed)
    loop, bf16_done = serve_phase("bf16", cfg, mesh, params, reqs, clock, device)
    if loop.stats["prefix_hits"] < 1:
        raise RuntimeError("bf16: the shared-prefix requests never hit the radix cache")
    n_pages = loop.pool_pages
    del loop
    gc.collect()

    prompt = reqs[0].prompt[:PREFIX]
    for kv_dtype in ("bf16", "int8", "fp8_e4m3"):
        c0, t0 = clock.secs, time.perf_counter()
        got = {
            impl: chunk_logits(cfg, mesh, params, prompt, attn_impl=impl,
                               kv_dtype=kv_dtype, n_pages=n_pages)
            for impl in ("flash_kernel", "xla_chunked")
        }
        compare_logits(f"check-{kv_dtype}", got["flash_kernel"], got["xla_chunked"])
        print(f"[check-{kv_dtype}] compile_s={clock.secs - c0:.1f} "
              f"wall_s={time.perf_counter() - t0:.1f}", flush=True)
        gc.collect()

    int8_reqs = _fresh(reqs[4:10])
    loop, int8_done = serve_phase("int8", cfg, mesh, params, int8_reqs, clock,
                                  device, kv_dtype="int8")
    print(f"[int8] greedy agreement with the bf16 pool: "
          f"{_agreement(int8_done, bf16_done[4:10])} tokens", flush=True)
    del loop, params
    gc.collect()

    params = M.init_params(bpmm_cfg, jax.random.PRNGKey(seed))
    loop, _ = serve_phase("bpmm", bpmm_cfg, mesh, params, _fresh(reqs[4:8]),
                          clock, device)
    del loop, params
    gc.collect()


def run_four_chips(cfg, seed, clock, devices):
    """DisaggRouter over a 4-way page-sharded pool vs the one-device loop."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh, make_pages_mesh
    from repro.launch.serving import DisaggRouter
    from repro.models import model as M

    one = make_mesh((1, 1), ("data", "model"), devices=devices[:1])
    four = make_pages_mesh(4)
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    params1 = jax.device_put(params, NamedSharding(one, P()))
    params4 = jax.device_put(params, NamedSharding(four, P()))
    del params
    reqs = [r for r in workload(cfg.vocab, seed) if len(r.prompt) <= 2048][:6]
    for r in reqs:
        r.arrival = 0

    single, single_done = serve_phase(
        "single-1chip", cfg, one, params1, _fresh(reqs), clock, devices[0],
        batch=4,
    )
    dis, dis_done = serve_phase(
        "disagg-4chip", cfg, four, params4, _fresh(reqs), clock, devices[0],
        attn_impl="xla_chunked", loop_cls=DisaggRouter, batch=4,
        prefill_batch=2, pool_pages=single.pool_pages,
    )
    print(f"[four] greedy agreement disagg vs single: "
          f"{_agreement(dis_done, single_done)} tokens", flush=True)

    rows = dis.pool_pages * dis.page
    leaf = jax.tree.leaves(dis._pools)[0]
    seen = {}
    for sh in leaf.addressable_shards:
        lo, hi, _ = sh.index[1].indices(rows)
        seen[sh.device.id] = (lo // dis.page, hi // dis.page)
        if sh.data.shape[1] != hi - lo:
            raise RuntimeError(f"shard on {sh.device} holds {sh.data.shape}")
    ranges = sorted(seen.values())
    want = [(i * dis.pool_pages // 4, (i + 1) * dis.pool_pages // 4) for i in range(4)]
    print(f"[four] pool leaf {leaf.shape} page ranges by device id: {seen}",
          flush=True)
    if len(seen) != 4 or ranges != want:
        raise RuntimeError(f"pool shards {seen} are not 4 distinct page ranges {want}")
    if dis.stats.get("pool_shards") != 4:
        raise RuntimeError(f"host allocator shards: {dis.stats.get('pool_shards')}")

    prompt = reqs[0].prompt[:PREFIX]
    ref = chunk_logits(cfg, one, params1, prompt, attn_impl="flash_kernel",
                       kv_dtype="bf16", n_pages=single.pool_pages)
    test = chunk_logits(cfg, four, params4, prompt, attn_impl="xla_chunked",
                        kv_dtype="bf16", n_pages=dis.pool_pages)
    compare_logits("four", test, ref)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the page-sharded path and its reference")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = _require_tpu(args.chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs import registry
    from repro.launch.compile_cache import place_compile_cache
    from repro.launch.mesh import make_mesh

    print(f"devices: {devices[:args.chips]}", flush=True)
    cache_dir = place_compile_cache(ROOT)
    print(f"compile cache: {cache_dir}", flush=True)
    clock = CompileClock()
    cfg = registry.get(ARCH)
    if args.chips == 4:
        run_four_chips(cfg, args.seed, clock, devices)
    else:
        mesh = make_mesh((1, 1), ("data", "model"), devices=devices[:1])
        run_one_chip(cfg, registry.get(BPMM_ARCH), mesh, args.seed, clock,
                     devices[0])
    n_cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"compile cache: {cache_dir} holds {n_cached} entries; "
          f"compile_s total {clock.secs:.1f}", flush=True)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devices),
    }}))


if __name__ == "__main__":
    main()
