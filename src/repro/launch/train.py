"""End-to-end trainer: jit'd train step (FSDP/TP/CP/EP sharded, donated,
remat'd, microbatched, optionally wire-compressed across pods) + a
fault-tolerant driver loop (auto-resume, async checkpoints, straggler
detection, restart supervision).

CLI::

    PYTHONPATH=src python -m repro.launch.train --arch fabnet-base \
        --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import dataclasses
import logging
import time
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import DataConfig, global_batch
from repro.distributed.fault_tolerance import RestartPolicy, StragglerDetector, run_with_restarts
from repro.distributed import sharding as shd
from repro.models import model as M
from repro.models import transformer as tf
from repro.models.config import ModelConfig
from repro.optim import AdamWConfig, adamw_init, adamw_update, cosine_schedule
from repro.optim.compression import ef_compress_tree, dequantize_int8, psum_compressed

log = logging.getLogger("repro.train")

__all__ = ["TrainHParams", "make_train_state_specs", "make_train_step", "train_loop"]


@dataclasses.dataclass(frozen=True)
class TrainHParams:
    peak_lr: float = 3e-4
    warmup: int = 100
    total_steps: int = 10_000
    adamw: AdamWConfig = AdamWConfig()
    # gradient compression across the pod axis: off | simulate | wire
    compression: str = "off"


def _batch_sharding(mesh: Mesh):
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return NamedSharding(mesh, P(axes))


def make_train_state_specs(cfg: ModelConfig, hp: TrainHParams):
    """ParamSpec tree for the full train state (params + moments + step)."""
    pspecs = M.build_specs(cfg)
    state = {
        "params": pspecs,
        "opt": {"mu": pspecs, "nu": pspecs, "count": shd.ParamSpec((), (), init="zeros")},
        "step": shd.ParamSpec((), (), init="zeros"),
    }
    if hp.compression != "off":
        state["err"] = pspecs
    return state


def init_train_state(cfg: ModelConfig, hp: TrainHParams, key: jax.Array):
    params = M.init_params(cfg, key)
    state: dict[str, Any] = {
        "params": params,
        "opt": adamw_init(params, hp.adamw),
        "step": jnp.zeros((), jnp.int32),
    }
    if hp.compression != "off":
        state["err"] = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return state


def abstract_train_state(cfg: ModelConfig, hp: TrainHParams):
    pdt = jnp.dtype(cfg.param_dtype)
    mdt = jnp.dtype(hp.adamw.moment_dtype)
    pspecs = M.build_specs(cfg)
    ab = lambda dt: shd.abstract_tree(pspecs, dt)
    state = {
        "params": ab(pdt),
        "opt": {
            "mu": ab(mdt),
            "nu": ab(mdt),
            "count": jax.ShapeDtypeStruct((), jnp.int32),
        },
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }
    if hp.compression != "off":
        state["err"] = ab(jnp.float32)
    return state


def train_state_shardings(cfg: ModelConfig, hp: TrainHParams, mesh: Mesh):
    pspecs = M.build_specs(cfg)
    ps = shd.sharding_tree(pspecs, mesh, M.rules_for(cfg))
    scalar = NamedSharding(mesh, P())
    state = {
        "params": ps,
        "opt": {"mu": ps, "nu": ps, "count": scalar},
        "step": scalar,
    }
    if hp.compression != "off":
        state["err"] = ps
    return state


def _grads_fn(cfg: ModelConfig, rt, params, batch, accum: int, pshard=None):
    """Mean loss gradient, microbatched when accum > 1 (scan keeps HLO small
    and caps activation memory at one microbatch)."""

    def loss(p, mb):
        if cfg.cast_params_once and pshard is not None:
            # sharded-local downcast pinned by a sharding constraint, so the
            # FSDP all-gathers downstream move bf16 instead of f32 masters
            cdt = jnp.dtype(cfg.dtype)
            p = jax.tree.map(
                lambda x, s: (
                    jax.lax.with_sharding_constraint(x.astype(cdt), s)
                    if x.dtype == jnp.float32 and x.ndim >= 2
                    else x
                ),
                p,
                pshard,
            )
        l, metrics = tf.loss_fn(p, cfg, mb, rt)
        return l, metrics

    def _pin(grads):
        # pin gradient shardings to the (FSDP-sharded) param shardings so the
        # partitioner can reduce-scatter dW instead of all-reducing it at
        # full size (ZeRO-2 semantics)
        if cfg.cast_params_once and pshard is not None:
            return jax.tree.map(jax.lax.with_sharding_constraint, grads, pshard)
        return grads

    if accum == 1:
        (l, metrics), grads = jax.value_and_grad(loss, has_aux=True)(params, batch)
        return _pin(grads), l, metrics

    def micro(carry, mb):
        g_acc, l_acc = carry
        (l, metrics), g = jax.value_and_grad(loss, has_aux=True)(params, mb)
        g_acc = jax.tree.map(lambda a, b: a + b, g_acc, _pin(g))
        return (g_acc, l_acc + l), metrics

    mbs = jax.tree.map(
        lambda x: x.reshape(accum, x.shape[0] // accum, *x.shape[1:]), batch
    )
    zero_g = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (g_sum, l_sum), metrics = jax.lax.scan(micro, (zero_g, 0.0), mbs)
    grads = jax.tree.map(lambda g: g / accum, g_sum)
    metrics = jax.tree.map(lambda m: m[-1], metrics)
    return grads, l_sum / accum, metrics


def make_train_step(
    cfg: ModelConfig, mesh: Mesh, hp: TrainHParams, batch_example=None
):
    """Returns (jitted step_fn(state, batch) -> (state, metrics),
    state_shardings, batch_shardings).  `batch_example` (a tree of arrays or
    ShapeDtypeStructs) fixes the batch structure for archs with modality
    inputs (frames / img_embeds); defaults to {tokens, labels}."""
    rt = M.resolve_runtime(cfg, mesh)
    accum = max(cfg.grad_accum, 1)
    st_shardings = train_state_shardings(cfg, hp, mesh)

    def step_fn(state, batch):
        lr = cosine_schedule(
            state["step"], peak_lr=hp.peak_lr, warmup=hp.warmup, total=hp.total_steps
        )

        pshard = st_shardings["params"]
        if hp.compression == "wire" and "pod" in mesh.axis_names:
            # per-pod grads + int8 error-feedback all-reduce across pods
            def pod_grads(params, err, batch):
                g, l, metrics = _grads_fn(cfg, rt, params, batch, accum)
                g_sync, new_err = psum_compressed(g, err, "pod")
                return g_sync, new_err, l, metrics

            grads, new_err, l, metrics = jax.shard_map(
                pod_grads,
                mesh=mesh,
                in_specs=(P(), P(), P("pod")),
                out_specs=(P(), P(), P(), P()),
                axis_names={"pod"},
            )(state["params"], state["err"], batch)
            l = jnp.mean(l)
            metrics = jax.tree.map(jnp.mean, metrics)
        else:
            grads, l, metrics = _grads_fn(
                cfg, rt, state["params"], batch, accum, pshard=pshard
            )
            new_err = None
            if hp.compression == "simulate":
                # numerically-faithful EF int8 (wire bytes unchanged in HLO)
                q, s, new_err = ef_compress_tree(grads, state["err"])
                grads = jax.tree.map(dequantize_int8, q, s)

        new_params, new_opt, stats = adamw_update(
            grads, state["opt"], state["params"], lr, hp.adamw
        )
        new_state = {
            "params": new_params,
            "opt": new_opt,
            "step": state["step"] + 1,
        }
        if new_err is not None:
            new_state["err"] = new_err
        metrics = dict(metrics)
        metrics.update(stats)
        metrics["lr"] = lr
        metrics["loss_total"] = l
        return new_state, metrics

    if batch_example is None:
        b_shard = _batch_sharding(mesh)
        batch_shardings = {"tokens": b_shard, "labels": b_shard}
    else:
        batch_shardings = shd.data_shardings(batch_example, mesh)
    step = jax.jit(
        step_fn,
        in_shardings=(st_shardings, batch_shardings),
        out_shardings=(st_shardings, None),
        donate_argnums=(0,),
    )
    return step, st_shardings, batch_shardings


# --------------------------------------------------------------------------
# Fault-tolerant driver
# --------------------------------------------------------------------------


def train_loop(
    cfg: ModelConfig,
    mesh: Mesh,
    hp: TrainHParams,
    data_cfg: DataConfig,
    *,
    steps: int,
    ckpt_dir: str | None = None,
    ckpt_every: int = 50,
    seed: int = 0,
    log_every: int = 10,
):
    """Resumable training: restores the latest committed checkpoint if one
    exists, otherwise initialises; saves asynchronously; flags stragglers."""
    step_fn, st_shardings, _ = make_train_step(cfg, mesh, hp)
    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
    detector = StragglerDetector()

    start = 0
    state = None
    if mgr is not None:
        abstract = abstract_train_state(cfg, hp)
        got_step, got = mgr.restore_latest(abstract, st_shardings)
        if got is not None:
            start, state = got_step, got
            log.info("resumed from step %d", start)
    if state is None:
        with mesh:
            state = init_train_state(cfg, hp, jax.random.PRNGKey(seed))
            state = jax.tree.map(jax.device_put, state, st_shardings)

    history = []
    for step in range(start, steps):
        batch = global_batch(data_cfg, step, mesh)
        t0 = time.monotonic()
        state, metrics = step_fn(state, batch)
        metrics = jax.device_get(metrics)
        dt = time.monotonic() - t0
        if detector.record(dt):
            log.warning("straggler pattern at step %d (%.2fs vs median %.2fs)",
                        step, dt, detector.median())
        history.append(float(metrics["loss"]))
        if log_every and step % log_every == 0:
            log.info("step %d loss %.4f gnorm %.3f lr %.2e (%.2fs)",
                     step, metrics["loss"], metrics["grad_norm"], metrics["lr"], dt)
        if mgr is not None and (step + 1) % ckpt_every == 0:
            mgr.save(step + 1, state)
    if mgr is not None:
        mgr.save(steps, state, blocking=True)
    return state, history


def main():
    from repro.configs import registry

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--compression", default="off")
    ap.add_argument("--reduced", action="store_true", help="smoke-size config")
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(name)s %(message)s")
    cfg = registry.get(args.arch, reduced=args.reduced)
    from repro.launch.mesh import make_local_mesh

    mesh = make_local_mesh()
    hp = TrainHParams(peak_lr=args.lr, total_steps=args.steps, compression=args.compression)
    data_cfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch)
    _, hist = train_loop(cfg, mesh, hp, data_cfg, steps=args.steps, ckpt_dir=args.ckpt_dir)
    print(f"final loss: {hist[-1]:.4f} (from {hist[0]:.4f})")


if __name__ == "__main__":
    main()
