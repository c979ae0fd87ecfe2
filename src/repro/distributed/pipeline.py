"""GPipe-style pipeline parallelism via shard_map + ppermute.

Each device along the `pipe` mesh axis owns one stage's parameters; the
microbatch stream flows through `M + S - 1` ticks with activations handed to
the next stage by collective_permute.  Bubble fraction = (S-1)/(M+S-1), so
callers pick M >= 4*S.  This is the optional third parallelism tier for
meshes configured as (pipe, data, model); the 40-cell dry-run meshes are
(pod, data, model), and PP is exercised by its own test/benchmark.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["gpipe"]


def gpipe(
    stage_fn: Callable,
    stage_params,
    microbatches: jax.Array,  # (M, mb, ...) — the microbatch stream
    mesh: Mesh,
    axis: str = "pipe",
):
    """Run `stage_fn(params_i, x)` as an S-deep pipeline over `axis`.

    stage_params: pytree with leading dim S (one slice per stage).
    Returns (M, mb, ...) outputs (replicated along `axis`).
    """
    s = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    m = microbatches.shape[0]

    def shard_fn(params_local, stream_local):
        params_local = jax.tree.map(lambda a: a[0], params_local)  # (1,...) -> (...)
        idx = jax.lax.axis_index(axis)
        zero = jnp.zeros_like(stream_local[0])
        carry = zero
        collected = []
        perm = [(i, (i + 1) % s) for i in range(s)]
        for t in range(m + s - 1):
            # stage 0 ingests microbatch t (beyond M: dead ticks)
            feed = stream_local[t] if t < m else zero
            inp = jnp.where(idx == 0, feed, carry)
            out = stage_fn(params_local, inp)
            carry = jax.lax.ppermute(out, axis, perm)
            if t >= s - 1:  # emitted by the last stage at these ticks
                collected.append(jnp.where(idx == s - 1, out, jnp.zeros_like(out)))
        stacked = jnp.stack(collected)  # (M, mb, ...)
        # replicate the result: only the last stage holds nonzero values
        return jax.lax.psum(stacked, axis)

    fn = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
    )
    return fn(stage_params, microbatches)
