"""Fused multilayer-dataflow BPMM kernel (Pallas, TPU target).

This kernel IS the paper's §IV orchestration re-expressed for TPU: all
butterfly stages of one slice piece execute back-to-back on a VMEM-resident
token tile.  The radix-2 stages are grouped into two block-diagonal
super-stages (R then L — see :mod:`repro.core.monarch`), each a batch of dense
``b x b`` / ``nb x nb`` MXU matmuls; the stride-wider-than-a-block swap set is
the single in-register axis flip between the two einsums (the multi-line-SPM,
transpose-free analogue).  The intermediate vector never touches HBM —
exactly one HBM read of x and one HBM write of y per token tile, vs one
round-trip *per stage* for the faithful staged form (paper Fig. 2's
cache-pressure pathology).

Grid = (token tiles, gout slices); the token-tile axis is the paper's
coarse-grained streaming dimension (§V-A): iterations pour through the kernel
while the TPU's DMA engine double-buffers the next tile against MXU compute —
the {Load | Cal | Store} decoupling.

Layouts:
    x: (T, gin, nb, b)            token-major, slice grid flattened
    r: (gout, gin, nb, b, b)      super-stage R, block-diagonal over hi
    l: (gout, gin, b, nb, nb)     super-stage L, block-diagonal over lo
    y: (T, gout, nb, b)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["monarch_bpmm", "pick_token_tile"]


# Mosaic's default scoped-VMEM limit for one kernel on TPU v5e; a kernel whose
# blocks and temporaries exceed it is refused at compile time
_SCOPED_VMEM = 16 * 1024 * 1024


def _padded(rows: int, lanes: int) -> int:
    """Elements a (rows, lanes) trailing tile occupies in VMEM: TPU vregs
    tile the last two dims (8, 128), so b=32 lanes cost 128."""
    return -(-rows // 8) * 8 * (-(-lanes // 128) * 128)


def pick_token_tile(gin: int, nb: int, b: int, dtype_bytes: float = 4) -> int:
    """Largest token tile whose VMEM footprint fits the scoped limit.

    The footprint is priced at the PADDED (8, 128) layout: per token, the
    x block (gin slabs of (nb, b)) and the y block (one slab), each
    double-buffered by the pipeline, plus one f32 slab per input slice for
    the upcasts of the unrolled slice loop; fixed, the r and l blocks
    (double-buffered) and one slice's f32 upcasts of them.

    ``dtype_bytes`` must come from the ACTUAL storage dtype (bf16 blocks
    are half the bytes of f32); the f32 default is the conservative choice
    for callers without an array in hand.  Fractional widths are legal and
    price a block at that effective width."""
    slab = _padded(nb, b)
    per_token = 2 * (gin + 1) * slab * float(dtype_bytes) + 4 * gin * slab
    weights = 2 * gin * nb * _padded(b, b) * 2 * float(dtype_bytes)
    weights += 2 * nb * _padded(b, b) * 4
    tile = int((_SCOPED_VMEM - weights) // max(per_token, 1.0))
    for cand in (512, 256, 128, 64, 32, 16, 8):
        if cand <= tile:
            return cand
    return 8


def _kernel(x_ref, r_ref, l_ref, y_ref, *, gin: int):
    acc = None
    for g in range(gin):  # static unroll over input slices (Fig. 10 sum)
        x = x_ref[:, g].astype(jnp.float32)  # (TB, nb, b)
        r = r_ref[0, g].astype(jnp.float32)  # (nb, b, b)
        l = l_ref[0, g].astype(jnp.float32)  # (b, nb, nb)
        # super-stage R: mix lo within each hi block  (batched b x b MXU)
        u = jnp.einsum("thj,hij->thi", x, r, preferred_element_type=jnp.float32)
        # super-stage L: mix hi per lo — the axis flip happens in VMEM
        v = jnp.einsum("tkj,jhk->thj", u, l, preferred_element_type=jnp.float32)
        acc = v if acc is None else acc + v
    y_ref[:, 0] = acc.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("token_tile", "interpret"))
def monarch_bpmm(
    x: jax.Array,
    r: jax.Array,
    l: jax.Array,
    *,
    token_tile: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """x: (T, gin, nb, b) -> y: (T, gout, nb, b).  T must divide by the tile
    (the ops wrapper pads)."""
    t, gin, nb, b = x.shape
    gout = r.shape[0]
    tb = token_tile or pick_token_tile(
        gin, nb, b, dtype_bytes=jnp.dtype(x.dtype).itemsize
    )
    if t % tb:
        raise ValueError(f"token count {t} not divisible by tile {tb}")

    grid = (t // tb, gout)
    return pl.pallas_call(
        functools.partial(_kernel, gin=gin),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tb, gin, nb, b), lambda i, o: (i, 0, 0, 0)),
            pl.BlockSpec((1, gin, nb, b, b), lambda i, o: (o, 0, 0, 0, 0)),
            pl.BlockSpec((1, gin, b, nb, nb), lambda i, o: (o, 0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((tb, 1, nb, b), lambda i, o: (i, o, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((t, gout, nb, b), x.dtype),
        interpret=interpret,
    )(x, r, l)
