"""Quantized paged-KV helpers: per-row symmetric quantization for page pools.

The paged cache stores K/V pages at a reduced ``kv_dtype`` (int8 or fp8_e4m3)
next to a small float32 scale tensor with one entry per (row, kv_head) —
``scale[r, h]`` reconstructs row ``r`` of head ``h`` as ``q * scale``.  The
granularity is deliberate: decode appends ONE row per step into a partially
filled page, so a true per-page scale would have to requantize every
previously written row on each append (either an extra gather/rescale/scatter
per decode step or compounding rounding error across up to ``page``
requantizations).  Per-row scales make every write independent, and because
the scale leaves are paged like the KV rows (stacked by layer, one lane row
per page and kv head: (L, KV, n_pages, 1, page) beside the KV leaves' (L,
KV, n_pages * page, hd)) they ride the page tables for free — copy-on-write
page copies, radix prefix aliasing, mod-window rings, and the sharded pool's
ownership ``transfer()`` all carry scales without knowing they exist.

Schemes (both symmetric, zero-point-free — attention rows are centred):

* ``int8``:     ``scale = absmax / 127``, values rounded and clipped.
* ``fp8_e4m3``: ``scale = absmax / 448`` (the e4m3 finite max), scaled cast —
  the mantissa keeps ~3 bits, the shared exponent headroom comes from the
  scale.
* ``bf16``:     the unquantized passthrough — no scale leaves exist and every
  code path compiles the exact PR-9 graph (bit-identity is a test contract).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "KV_DTYPES",
    "INT8_MAX",
    "FP8_MAX",
    "validate_kv_dtype",
    "kv_store_dtype",
    "quantize_rows",
    "dequantize_rows",
]

KV_DTYPES = ("bf16", "int8", "fp8_e4m3")
INT8_MAX = 127.0
FP8_MAX = 448.0  # largest finite float8_e4m3fn


def validate_kv_dtype(kv_dtype: str) -> str:
    if kv_dtype not in KV_DTYPES:
        raise ValueError(
            f"kv_dtype must be one of {KV_DTYPES}, got {kv_dtype!r}"
        )
    return kv_dtype


def kv_store_dtype(kv_dtype: str, base_dtype) -> jnp.dtype:
    """The dtype pool pages are STORED at for ``kv_dtype`` (``base_dtype`` is
    the model's compute/cache dtype, returned unchanged for 'bf16')."""
    validate_kv_dtype(kv_dtype)
    if kv_dtype == "int8":
        return jnp.dtype(jnp.int8)
    if kv_dtype == "fp8_e4m3":
        return jnp.dtype(jnp.float8_e4m3fn)
    return jnp.dtype(base_dtype)


def _qmax(store_dtype) -> float:
    store_dtype = jnp.dtype(store_dtype)
    if store_dtype == jnp.dtype(jnp.int8):
        return INT8_MAX
    if store_dtype == jnp.dtype(jnp.float8_e4m3fn):
        return FP8_MAX
    raise ValueError(f"no quantization scheme for store dtype {store_dtype}")


def quantize_rows(x: jax.Array, store_dtype) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-row quantization over the last (head_dim) axis.

    x: (..., hd) float -> (q: (..., hd) ``store_dtype``, scale: (...,) f32)
    with ``q * scale ~= x``.  All-zero rows keep scale 1 (q is 0 anyway), so
    dequantizing never divides by or multiplies with a zero scale."""
    qmax = _qmax(store_dtype)
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(absmax > 0, absmax / qmax, 1.0).astype(jnp.float32)
    y = xf / scale[..., None]
    if jnp.dtype(store_dtype) == jnp.dtype(jnp.int8):
        q = jnp.clip(jnp.round(y), -qmax, qmax).astype(store_dtype)
    else:
        q = y.astype(store_dtype)
    return q, scale


def dequantize_rows(q: jax.Array, scale: jax.Array, dtype=jnp.float32) -> jax.Array:
    """Inverse of :func:`quantize_rows`: q (..., hd) x scale (...,) -> float."""
    return (q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)).astype(dtype)
