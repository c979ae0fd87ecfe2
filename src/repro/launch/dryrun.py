import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: the SPMD
partitioner must accept every sharding, the compiled module must fit, and the
cost/memory/collective numbers feed EXPERIMENTS.md §Dry-run and §Roofline.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun.jsonl
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-72b --multi-pod
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.core import attention as attn
from repro.configs.shapes import SHAPES, Shape, applicable, batch_specs
from repro.launch import analysis
from repro.launch.mesh import make_production_mesh
from repro.launch.serve import abstract_cache, cache_shardings
from repro.launch.train import (
    TrainHParams,
    abstract_train_state,
    make_train_step,
    train_state_shardings,
)
from repro.distributed import sharding as shd
from repro.models import model as M
from repro.models import transformer as tf
from repro.models.config import ModelConfig
from jax.sharding import NamedSharding, PartitionSpec as P


def lower_cell(cfg: ModelConfig, shape: Shape, mesh, hp: TrainHParams | None = None):
    """Lower one (arch x shape) on `mesh`; returns the jax Lowered object and
    the analytic model-flops for the step."""
    rt = M.resolve_runtime(cfg, mesh)
    hp = hp or TrainHParams()
    bspecs = batch_specs(cfg, shape)
    b_shard = shd.data_shardings(bspecs, mesh)

    if shape.kind == "train":
        step, st_sh, b_sh = make_train_step(cfg, mesh, hp, batch_example=bspecs)
        ab_state = abstract_train_state(cfg, hp)
        lowered = step.lower(ab_state, bspecs)
        tokens = shape.batch * shape.seq
        mf = M.model_flops_per_token(cfg, shape.seq, mode="train") * tokens
        return lowered, mf

    pspecs = M.build_specs(cfg)
    p_shard = shd.sharding_tree(pspecs, mesh, M.rules_for(cfg))
    ab_params = M.abstract_params(cfg)

    if shape.kind == "prefill":
        logit_shard = shd.sharding_for((shape.batch, cfg.vocab), ("batch", None), mesh)
        fn = jax.jit(
            lambda params, b: tf.prefill(params, cfg, b, rt, cache_len=shape.seq),
            in_shardings=(p_shard, b_shard),
            out_shardings=(logit_shard, cache_shardings(cfg, mesh, shape.batch, shape.seq)),
        )
        lowered = fn.lower(ab_params, bspecs)
        tokens = shape.batch * shape.seq
        mf = M.model_flops_per_token(cfg, shape.seq, mode="fwd") * tokens
        return lowered, mf

    # decode: one token against a seq_len-deep cache
    c_shard = cache_shardings(cfg, mesh, shape.batch, shape.seq)
    ab_caches = abstract_cache(cfg, shape.batch, shape.seq)
    rep = NamedSharding(mesh, P())
    logit_shard = shd.sharding_for((shape.batch, cfg.vocab), ("batch", None), mesh)
    fn = jax.jit(
        lambda params, caches, toks, pos: tf.decode_step(params, cfg, caches, toks, pos, rt),
        in_shardings=(p_shard, c_shard, b_shard["tokens"], rep),
        out_shardings=(logit_shard, c_shard),
        donate_argnums=(1,),
    )
    toks = bspecs["tokens"]
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    lowered = fn.lower(ab_params, ab_caches, toks, pos)
    mf = M.decode_flops_per_token(cfg, shape.seq) * shape.batch
    return lowered, mf


def _probe_cfg(cfg: ModelConfig, k: int) -> ModelConfig:
    """k-period unrolled cost-probe variant of cfg."""
    import dataclasses

    period = len(cfg.period_slots)
    kw = dict(
        n_layers=k * period,
        unroll_layers=True,
        grad_accum=1,
    )
    if cfg.family == "encdec" and cfg.n_enc_layers:
        kw["n_enc_layers"] = max(1, cfg.n_enc_layers * k * period // cfg.n_layers)
    return dataclasses.replace(cfg, **kw)


def _probe_cost(cfg: ModelConfig, shape: Shape, mesh, k: int) -> dict:
    lowered, _ = lower_cell(_probe_cfg(cfg, k), shape, mesh)
    compiled = lowered.compile()
    text = compiled.as_text()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    coll = analysis.collective_bytes(text)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": float(coll["total"]),
        "coll_by_kind": {kk: coll[kk] for kk in
                         ("all-gather", "all-reduce", "reduce-scatter",
                          "all-to-all", "collective-permute")},
    }


def _attention_stage(cfg: ModelConfig, shape: Shape) -> dict | None:
    """Analytic fwd FLOP/HBM-byte accounting for the attention softmax stage
    under both execution forms.  The fused Pallas kernel is invisible to XLA's
    ``cost_analysis`` (a near-zero-cost custom call), so the dry-run roofline
    models it from :mod:`repro.core.attention` instead."""
    n_attn = sum(1 for s in cfg.period_slots if s.mixer == "attn") * cfg.n_periods
    if not n_attn or not cfg.n_heads:
        return None
    if shape.kind == "decode":
        s_q, s_kv, causal = 1, shape.seq, False
        if cfg.sliding_window:
            s_kv = min(s_kv, cfg.sliding_window)
    else:
        s_q = s_kv = shape.seq
        causal = cfg.causal
    win = cfg.sliding_window if causal else None
    args = (shape.batch, s_q, s_kv, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim)
    spec = cfg.attention_spec
    flops = n_attn * attn.attention_flops(
        shape.batch, s_q, s_kv, cfg.n_heads, cfg.head_dim, causal=causal,
        window=win, pattern=spec.pattern, pattern_arg=spec.pattern_arg,
        q_tile=spec.q_tile, kv_tile=spec.kv_tile,
    )
    out = {"flops": flops, "n_attn_layers": n_attn, "pattern": spec.pattern}
    if spec.sparse:
        from repro.core import sparsity

        out["kv_density"] = sparsity.pattern_kv_density(
            spec.pattern, s_q if s_q > 1 else s_kv, s_kv, spec.q_tile,
            spec.kv_tile, causal=causal, window=win,
            pattern_arg=spec.pattern_arg,
        )
    for impl in attn.IMPLS:
        out[impl] = {
            "hbm_bytes": n_attn * attn.attention_hbm_bytes(
                dataclasses.replace(spec, impl=impl), *args, causal=causal, window=win
            )
        }
    return out


def _kv_cache_stage(cfg: ModelConfig, shape: Shape) -> dict | None:
    """KV-cache HBM accounting for serving shapes, priced both ways.

    ``dense_reserved_bytes`` is the contiguous engine's cost: every slot
    reserves ``cache_len`` rows regardless of pattern — capacity is priced at
    worst-case dense length.  ``paged_resident_bytes`` prices the paged
    engine: per request, the PEAK simultaneously-live page count under the
    pattern's retention schedule (:func:`repro.core.sparsity.
    page_peak_resident` — the admission reservation), times the page size.
    ``paged_live_read_bytes`` is the steady-state *read* set (block-map
    density x pages — what one decode step actually streams).  The ratio of
    the first two is the concurrent-request capacity win at a fixed HBM
    budget (the serve_throughput ``paged_capacity`` gate measures it live).

    One page table serves every layer, so retention is the UNION of the
    per-slot patterns' last-reader schedules (``Slot.attn_pattern``
    overrides included) — exactly what ``ServeLoop._paged_schedule``
    reserves: a hybrid stack with one dense-causal slot prices at dense
    retention, not at the sparse slots' optimism.

    The ``prefix_*`` fields price the radix prefix cache under an assumed
    share ratio (half the prompt shared batch-wide): shared tiles resident
    once + per-request unique-suffix peaks, and the fraction of admission
    prefill FLOPs the cache absorbs — the analytic counterpart of the
    ``--check-prefix`` gate in ``benchmarks.serve_throughput``.

    ``shard_split`` prices the mesh-sharded pool at 2- and 4-way page
    sharding: per-shard peak resident pages (the balanced allocator's
    ``ceil(global / k)`` bound), per-shard resident bytes, and the
    per-shard capacity ratio — the analytic counterpart of the
    ``--check-shard`` gate.

    ``kv_dtype`` prices the SAME paged residency at each pool storage
    width (bf16 | int8 | fp8_e4m3, :func:`repro.core.attention.
    kv_dtype_bytes` — quantized widths include the amortized per-row f32
    scale): resident bytes, capacity ratio against the bf16 dense
    reservation, and the decode-step live read set — the analytic
    counterpart of the ``--check-quant`` gate."""
    import math

    from repro.core import sparsity

    n_attn = sum(1 for s in cfg.period_slots if s.mixer == "attn") * cfg.n_periods
    if not n_attn or not cfg.n_kv_heads or shape.kind not in ("decode", "prefill"):
        return None
    if cfg.sliding_window or cfg.family == "encdec":
        return None  # ring / cross caches keep the contiguous layout
    spec = cfg.attention_spec
    pattern, arg, _, win = sparsity.canonical_pattern(
        spec.pattern, spec.pattern_arg, True, None
    )
    s = shape.seq
    page = sparsity.pick_pattern_tiles(1, s, spec.q_tile, spec.kv_tile)[1]
    n_tiles = -(-s // page)
    pats = {
        sl.attn_pattern or spec.pattern
        for sl in cfg.period_slots
        if sl.mixer == "attn"
    }
    last = sparsity.page_last_reader_union(
        pats, s, spec.q_tile, page, pattern_arg=spec.pattern_arg
    )
    peak_pages = int(sparsity.page_residency(last, s, page).max())
    density = sparsity.pattern_kv_density(
        pattern, s, s, spec.q_tile, page, causal=True, window=win,
        pattern_arg=arg,
    ) if pattern != "dense" or win is not None else 1.0
    row_bytes = 2 * cfg.n_kv_heads * cfg.head_dim * jnp.dtype(cfg.dtype).itemsize
    per_layer_dense = shape.batch * s * row_bytes
    per_layer_paged = shape.batch * peak_pages * page * row_bytes
    live_read = shape.batch * max(math.ceil(density * n_tiles), 1) * page * row_bytes

    # --- prefix sharing (radix cache) under an assumed share ratio -------
    # Model the ROADMAP's system-prompt traffic shape: every request in the
    # batch shares the first ``share`` of its prompt.  Shared prefix tiles
    # are resident ONCE (the tree + every sharer alias one physical copy);
    # each request adds only its unique-suffix peak
    # (page_residency(start_tile) — the same quantity warm admission
    # reserves).  Prefill FLOPs saved uses the engine's analytic pricing:
    # after the first request, each sharer prefills only its suffix, whose
    # attention term starts at the divergence position.
    share = 0.5
    shared_tiles = int(share * s) // page
    shared_tokens = shared_tiles * page
    uniq_peak = (
        int(sparsity.page_residency(last, s, page, start_tile=shared_tiles).max())
        if shared_tiles < len(last) else 0
    )
    per_layer_shared = (
        shared_tiles * page + shape.batch * uniq_peak * page
    ) * row_bytes
    b = shape.batch
    per_tok = M.model_flops_per_token(cfg, 1, "fwd")
    attn_c = 4 * cfg.n_heads * cfg.head_dim * n_attn

    def _pf(t, pos0):  # analytic prefill FLOPs for t tokens at offset pos0
        return t * per_tok + attn_c * (t * pos0 + t * (t + 1) / 2)

    cold = b * _pf(s, 0)
    warm = _pf(s, 0) + (b - 1) * _pf(s - shared_tokens, shared_tokens)

    # --- mesh-sharded pool: per-shard pricing at 2- and 4-way ------------
    # A "pages" mesh axis splits the pool's page rows into k contiguous
    # ranges; the balanced host allocator keeps each shard's residency at
    # ceil(global / k) (page_residency's n_shards is that per-request
    # analytic bound), so each DEVICE holds a 1/k slice of the paged
    # resident set while dense reservations on the same mesh would shard
    # their full batch x cache_len rows the same way — the capacity ratio
    # is preserved per shard, and the absolute per-device bytes shrink.
    shard_split = {}
    for k in (2, 4):
        shard_peak = int(
            sparsity.page_residency(last, s, page, n_shards=k).max()
        )
        per_layer_shard = shape.batch * shard_peak * page * row_bytes
        shard_split[str(k)] = {
            "shard_peak_resident_pages": shard_peak,
            "shard_paged_resident_bytes": float(n_attn * per_layer_shard),
            "shard_dense_reserved_bytes": float(
                n_attn * per_layer_dense / k
            ),
            "shard_capacity_ratio": float(
                (per_layer_dense / k) / max(per_layer_shard, 1)
            ),
        }
    # --- pool storage width: the same residency at bf16 / int8 / fp8 ------
    kv_dtype_split = {}
    base_bytes = jnp.dtype(cfg.dtype).itemsize
    for kd in ("bf16", "int8", "fp8_e4m3"):
        eff = attn.kv_dtype_bytes(kd, cfg.head_dim, base_bytes=base_bytes)
        rb = 2 * cfg.n_kv_heads * cfg.head_dim * eff
        plp = shape.batch * peak_pages * page * rb
        lr = shape.batch * max(math.ceil(density * n_tiles), 1) * page * rb
        kv_dtype_split[kd] = {
            "effective_bytes_per_value": float(eff),
            "paged_resident_bytes": float(n_attn * plp),
            "decode_live_read_bytes": float(n_attn * lr),
            "capacity_ratio": float(per_layer_dense / max(plp, 1)),
        }

    return {
        "pattern": pattern,
        "retention_patterns": sorted(pats),
        "page_tokens": page,
        "n_tiles": n_tiles,
        "peak_resident_pages": peak_pages,
        "dense_reserved_bytes": float(n_attn * per_layer_dense),
        "paged_resident_bytes": float(n_attn * per_layer_paged),
        "paged_live_read_bytes": float(n_attn * live_read),
        "capacity_ratio": float(per_layer_dense / max(per_layer_paged, 1)),
        "prefix_share_ratio": share,
        "shared_prefix_tokens": shared_tokens,
        "shared_resident_pages": shared_tiles,
        "unique_peak_pages_per_request": uniq_peak,
        "prefix_resident_bytes": float(n_attn * per_layer_shared),
        "prefix_capacity_ratio": float(
            per_layer_paged / max(per_layer_shared, 1)
        ),
        "prefill_flops_saved_frac": float(1.0 - warm / max(cold, 1.0)),
        "shard_split": shard_split,
        "kv_dtype": kv_dtype_split,
    }


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    reduced: bool = False,
    cfg_override: ModelConfig | None = None,
    lower_only: bool = False,
    probes: bool = True,
    attn_impl: str | None = None,
    attn_pattern: str | None = None,
) -> dict:
    cfg = cfg_override or registry.get(arch, reduced=reduced)
    cfg = attn.override_attention(cfg, impl=attn_impl, pattern=attn_pattern)
    shape = SHAPES[shape_name]
    rec: dict = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "params": M.count_params(cfg),
    }
    ok, reason = applicable(cfg, shape)
    if not ok:
        rec.update(status="skipped", reason=reason)
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    # the fused kernel is one per device and cannot be partitioned over the
    # fake mesh: the modules lower the XLA form, and the roofline below
    # accounts the fused form analytically
    xla_cfg = attn.override_attention(cfg, impl="xla_chunked")
    t0 = time.monotonic()
    try:
        # --- 1. the real (scanned) module: compile-proof + memory analysis
        lowered, model_flops = lower_cell(xla_cfg, shape, mesh)
        t_lower = time.monotonic() - t0
        if lower_only:
            rec.update(status="lowered", t_lower_s=round(t_lower, 1), chips=chips)
            return rec
        compiled = lowered.compile()
        t_compile = time.monotonic() - t0 - t_lower
        mem = compiled.memory_analysis()
        full_coll = analysis.collective_bytes(compiled.as_text())

        rl = None
        p1 = p2 = None
        if probes:
            # --- 2. unrolled probes: per-period cost slope (XLA counts while
            # bodies once — ModelConfig.unroll_layers doc)
            p1 = _probe_cost(xla_cfg, shape, mesh, 1)
            p2 = _probe_cost(xla_cfg, shape, mesh, 2)
            n = cfg.n_periods
            extrap = {
                key: p1[key] + (n - 1) * (p2[key] - p1[key])
                for key in ("flops", "bytes", "coll")
            }
            rl = analysis.Roofline(
                flops=extrap["flops"],
                hbm_bytes=extrap["bytes"],
                coll_bytes=extrap["coll"],
                chips=chips,
                model_flops=model_flops / chips,
            )
        # attention-stage accounting: the probes lower the XLA chunked form
        # (the kernel is single-device); when flash_kernel is configured the
        # roofline swaps the chunked stage's score traffic for the fused
        # kernel's streaming traffic (per-device share)
        stage = _attention_stage(cfg, shape)
        if stage and rl and cfg.attention.fused:
            delta = (
                stage["flash_kernel"]["hbm_bytes"]
                - stage["xla_chunked"]["hbm_bytes"]
            ) / chips
            rl = dataclasses.replace(rl, hbm_bytes=max(rl.hbm_bytes + delta, 0.0))
        rec["attention_stage_fwd"] = stage
        rec["kv_cache"] = _kv_cache_stage(cfg, shape)
        rec.update(
            status="ok",
            t_lower_s=round(t_lower, 1),
            t_compile_s=round(t_compile, 1),
            chips=chips,
            memory={
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "peak_est_bytes": mem.argument_size_in_bytes
                + mem.output_size_in_bytes
                + mem.temp_size_in_bytes
                - mem.alias_size_in_bytes,
            },
            collectives_full_module=dict(full_coll),
            probe_1p=p1,
            probe_2p=p2,
            roofline=rl.row() if rl else None,
        )
    except Exception as e:  # noqa: BLE001 — a failing cell is a bug to report
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-2000:])
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--lower-only", action="store_true")
    ap.add_argument("--no-probes", action="store_true")
    ap.add_argument("--attn", default=None, choices=["xla_chunked", "flash_kernel"],
                    help="override the attention execution form for every cell")
    ap.add_argument("--pattern", default=None,
                    choices=["dense", "causal", "window", "butterfly", "strided",
                             "global_window"],
                    help="override the attention block-sparsity pattern")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    archs = registry.ASSIGNED if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    out_f = open(args.out, "a") if args.out else None
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_cell(
                    arch, shape, mp, reduced=args.reduced,
                    lower_only=args.lower_only, probes=not args.no_probes,
                    attn_impl=args.attn, attn_pattern=args.pattern,
                )
                line = json.dumps(rec)
                print(_summ0(rec), flush=True)
                if out_f:
                    out_f.write(line + "\n")
                    out_f.flush()
    if out_f:
        out_f.close()


def _summ0(rec: dict) -> str:
    if rec["status"] == "ok" and rec.get("roofline"):
        return _summ(rec)
    if rec["status"] == "ok":
        return (f"[ok] {rec['arch']:18s} {rec['shape']:12s} {rec['mesh']:8s} "
                f"compile={rec['t_compile_s']:.0f}s "
                f"mem/dev={rec['memory']['peak_est_bytes']/2**30:.2f}GiB (no probes)")
    if rec["status"] == "lowered":
        return f"[lowered] {rec['arch']:18s} {rec['shape']:12s} {rec['mesh']:8s} t={rec['t_lower_s']}s"
    if rec["status"] == "skipped":
        return f"[skip] {rec['arch']:18s} {rec['shape']:12s} {rec['mesh']:8s} {rec['reason']}"
    return json.dumps(rec)[:800]


def _summ(rec: dict) -> str:
    r = rec["roofline"]
    m = rec["memory"]
    kv = rec.get("kv_cache")
    kv_s = (
        f" kv_cap={kv['capacity_ratio']:.1f}x"
        f"({kv['peak_resident_pages']}/{kv['n_tiles']}pg)"
        f" px@{kv['prefix_share_ratio']:.0%}="
        f"{kv['prefix_capacity_ratio']:.1f}x"
        f"(-{kv['prefill_flops_saved_frac']:.0%}flops)"
        if kv else ""
    )
    if kv and kv.get("kv_dtype"):
        kd = kv["kv_dtype"]
        kv_s += " qcap=" + "/".join(
            f"{name.split('_')[0]}:{kd[name]['capacity_ratio']:.1f}x"
            for name in ("bf16", "int8", "fp8_e4m3")
            if name in kd
        )
    return (
        f"[ok] {rec['arch']:18s} {rec['shape']:12s} {rec['mesh']:8s} "
        f"compile={rec['t_compile_s']:.0f}s mem/dev={m['peak_est_bytes']/2**30:.2f}GiB "
        f"t_comp={r['t_compute']*1e3:.2f}ms t_mem={r['t_memory']*1e3:.2f}ms "
        f"t_coll={r['t_collective']*1e3:.2f}ms dom={r['dominant']} "
        f"useful={r['useful_ratio']:.2f} roofline={r['roofline_fraction']:.2%}"
        f"{kv_s}"
    )


if __name__ == "__main__":
    main()
