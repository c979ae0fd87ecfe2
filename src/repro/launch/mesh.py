"""Production mesh builders (as functions — importing never touches jax
device state)."""

from __future__ import annotations

import jax

__all__ = [
    "make_mesh", "make_production_mesh", "make_local_mesh", "make_pages_mesh",
]


def make_mesh(shape, names, devices=None):
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD propagates the
    shardings the logical-axis rules leave open).  ``devices`` defaults to
    the first ``prod(shape)`` of ``jax.devices()``."""
    return jax.make_mesh(
        shape, names, axis_types=(jax.sharding.AxisType.Auto,) * len(names),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """A data-parallel mesh over every device of this host (CPU tests: 1
    device).  With more than one device the fused attention kernels are
    refused (``models.layers._fused``): build a one-device mesh with
    ``make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])`` or
    ask for ``attn_impl="xla_chunked"``."""
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))


def make_pages_mesh(n_shards: int):
    """Serve mesh with a ``pages`` axis: the paged KV pool's page rows shard
    ``n_shards``-way (see :func:`repro.models.transformer.paged_pool_specs`),
    remaining devices data-parallel.  CPU CI reaches 4 devices via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4``."""
    n = len(jax.devices())
    if n % n_shards:
        raise ValueError(
            f"{n} devices do not split into {n_shards} page shards"
        )
    return make_mesh((n // n_shards, 1, n_shards), ("data", "model", "pages"))
