"""Quantized paged KV cache: round-trip bounds, scale carriage through the
page machinery (CoW fork, ownership transfer, shard split), paged-vs-
contiguous attention error under per-dtype tolerances across patterns x
backends x modes (GQA included), and the bf16 bit-identity contract.

The contract under test: a pool stored at int8/fp8 with per-(row, kv_head)
scales must behave exactly like a bf16 pool up to the quantizer's rounding —
same liveness, same masks, same page sharing — and ``kv_dtype='bf16'`` must
compile the exact pre-quantization graph (no scale leaves, identical tokens).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core import quant, sparsity
from repro.core.attention import AttentionSpec, kv_dtype_bytes
from repro.kernels.monarch_bpmm import pick_token_tile
from repro.launch.mesh import make_local_mesh
from repro.launch.serve import PagePool, Request, ServeLoop
from repro.launch.serving.entries import zero_pools
from repro.models import model as M
from repro.models import transformer as tf
from repro.models.layers import (
    Runtime,
    run_attention,
    run_chunk_attention,
    run_decode_attention,
    run_paged_chunk_attention,
    run_paged_decode_attention,
    run_paged_prefill_attention,
)


def _f32(cfg):
    return dataclasses.replace(cfg, dtype="float32", capacity_factor=8.0)


STORE_DTYPES = [("int8", jnp.int8), ("fp8_e4m3", jnp.float8_e4m3fn)]


# --------------------------------------------------------------------------
# Quantize/dequantize round trip: per-row error bounds
# --------------------------------------------------------------------------


@pytest.mark.parametrize("name,store", STORE_DTYPES)
def test_round_trip_error_bounds(name, store):
    """Symmetric per-row quantization must bound the reconstruction error by
    the scheme's step size: absmax/(2*127) per row for int8, absmax/16 for
    fp8_e4m3 (3 mantissa bits -> half-ulp relative error 2^-4)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 2, 64), jnp.float32) * 7.3
    q, s = quant.quantize_rows(x, store)
    assert q.dtype == jnp.dtype(store) and s.dtype == jnp.float32
    assert s.shape == x.shape[:-1]
    xr = quant.dequantize_rows(q, s)
    err = jnp.max(jnp.abs(xr - x), axis=-1)
    absmax = jnp.max(jnp.abs(x), axis=-1)
    bound = absmax / 254.0 if name == "int8" else absmax / 16.0
    assert bool(jnp.all(err <= bound + 1e-6)), f"{name} exceeded its bound"


def test_round_trip_zero_rows_exact():
    """All-zero rows keep scale 1 and reconstruct exactly (never a 0 * 0/0)."""
    x = jnp.zeros((4, 2, 8), jnp.float32)
    q, s = quant.quantize_rows(x, jnp.int8)
    assert bool(jnp.all(s == 1.0))
    assert bool(jnp.all(quant.dequantize_rows(q, s) == 0.0))


def test_kv_dtype_validation_and_store():
    with pytest.raises(ValueError, match="kv_dtype"):
        quant.validate_kv_dtype("int4")
    assert quant.kv_store_dtype("bf16", jnp.float32) == jnp.dtype(jnp.float32)
    assert quant.kv_store_dtype("int8", jnp.float32) == jnp.dtype(jnp.int8)
    assert (
        quant.kv_store_dtype("fp8_e4m3", jnp.float32)
        == jnp.dtype(jnp.float8_e4m3fn)
    )
    # quantized widths price payload + amortized f32 scale per head_dim values
    assert kv_dtype_bytes("bf16", 64) == 2.0
    assert kv_dtype_bytes("int8", 64) == pytest.approx(1.0 + 4.0 / 64)
    assert kv_dtype_bytes("fp8_e4m3", 128) == pytest.approx(1.0 + 4.0 / 128)
    with pytest.raises(ValueError):
        kv_dtype_bytes("int4", 64)


# --------------------------------------------------------------------------
# Satellite: pick_token_tile budgets quantized tiles at their true width
# --------------------------------------------------------------------------


def test_pick_token_tile_quantized_width():
    """At a geometry pinched between tile candidates, the quantized effective
    width (1 + 4/hd bytes) must admit a strictly larger token tile than bf16
    — the VMEM budget prices true bytes, not container dtypes."""
    gin, nb, b = 2, 16, 32  # padded slab 16 x 128; 256 < tile(bf16) < 512
    t_bf16 = pick_token_tile(gin, nb, b, dtype_bytes=2.0)
    t_int8 = pick_token_tile(gin, nb, b, dtype_bytes=kv_dtype_bytes("int8", 64))
    assert t_int8 > t_bf16
    assert t_bf16 == 256 and t_int8 == 512
    # monotone: fp8 prices the same byte width as int8
    assert pick_token_tile(gin, nb, b, kv_dtype_bytes("fp8_e4m3", 64)) == t_int8
    # int dtype_bytes callers (the existing activation path) are unchanged
    assert pick_token_tile(gin, nb, b, 4) <= t_bf16


# --------------------------------------------------------------------------
# Scale carriage: CoW page copy, pool specs, zero_pools dtypes, transfer
# --------------------------------------------------------------------------


def test_paged_copy_page_carries_scales():
    """The device half of a CoW fork tree-maps every pool leaf — K/V rows
    and their scale rows move together, so a forked page can never read
    another page's scales."""
    page, n_pages, kv, hd = 4, 3, 2, 8
    rows = n_pages * page
    key = jax.random.PRNGKey(1)
    # stacked pool leaves as stored: K/V (L, KV, rows, hd), scales (L, KV,
    # n_pages, 1, page)
    caches = {
        "slot00": {
            "attn": {
                "k": jax.random.normal(key, (2, kv, rows, hd)),
                "v": jax.random.normal(key, (2, kv, rows, hd)),
                "k_scale": jax.random.uniform(key, (2, kv, n_pages, 1, page)) + 0.5,
                "v_scale": jax.random.uniform(key, (2, kv, n_pages, 1, page)) + 0.5,
            }
        }
    }

    def page_of(leaf, p):  # every layer's and head's rows of page p
        leaf = np.asarray(leaf)
        if leaf.ndim == 5:
            return leaf[:, :, p]
        return leaf[:, :, p * page:(p + 1) * page]

    out = tf.paged_copy_page(caches, jnp.int32(0), jnp.int32(2), page)
    for name in ("k", "v", "k_scale", "v_scale"):
        src = page_of(caches["slot00"]["attn"][name], 0)
        dst = page_of(out["slot00"]["attn"][name], 2)
        np.testing.assert_array_equal(src, dst, name)
        # untouched pages stay untouched
        np.testing.assert_array_equal(
            page_of(caches["slot00"]["attn"][name], 1),
            page_of(out["slot00"]["attn"][name], 1),
        )


def test_pool_specs_and_zero_pools_dtypes():
    """Quantized pool trees add f32 ``*_scale`` leaves next to the K/V pools
    they reconstruct; bf16 trees have none (the PR-9 layout, bit-for-bit).
    Cross pools stay unquantized by policy."""
    cfg = _f32(registry.get("qwen3-0.6b", reduced=True))
    mesh = make_local_mesh()
    base = tf.paged_pool_specs(cfg, 4, 8)
    q8 = tf.paged_pool_specs(cfg, 4, 8, kv_dtype="int8")
    for slot, sc in q8.items():
        assert set(sc["attn"]) == {"k", "v", "k_scale", "v_scale"}
        assert set(base[slot]["attn"]) == {"k", "v"}
        # one scale per (layer, kv head, row), a page's scales one lane row
        assert sc["attn"]["k_scale"].shape == (*sc["attn"]["k"].shape[:2], 4, 1, 8)
    with pytest.raises(ValueError, match="kv_dtype"):
        tf.paged_pool_specs(cfg, 4, 8, kv_dtype="int4")

    pools = zero_pools(cfg, mesh, 4, 8, kv_dtype="int8")
    for sc in pools.values():
        assert sc["attn"]["k"].dtype == jnp.int8
        assert sc["attn"]["v"].dtype == jnp.int8
        assert sc["attn"]["k_scale"].dtype == jnp.float32
    bfp = zero_pools(cfg, mesh, 4, 8, kv_dtype="bf16")
    ref = zero_pools(cfg, mesh, 4, 8)
    assert jax.tree_util.tree_structure(bfp) == jax.tree_util.tree_structure(ref)
    for a, b in zip(jax.tree_util.tree_leaves(bfp), jax.tree_util.tree_leaves(ref)):
        assert a.dtype == b.dtype and a.shape == b.shape


def test_transfer_relabels_without_touching_payload_keys():
    """Ownership transfer moves one host-side reference label; the physical
    page id — the key every device payload and scale row is addressed by —
    never changes, so quantized pages ride a handoff untouched."""
    pool = PagePool(8, n_shards=2)
    pid = pool.alloc("prefill:0")
    pool.transfer(pid, "prefill:0", "decode:0")
    assert pool.holders() == {"decode:0": 1}
    assert pool.page_refs(pid) == 1  # the count is untouched
    with pytest.raises(ValueError, match="holds no reference"):
        pool.transfer(pid, "prefill:0", "x")
    pool.release(pid, "decode:0")
    assert pool.in_use == 0


# --------------------------------------------------------------------------
# Paged-vs-contiguous attention error across patterns x impls x modes (GQA)
# --------------------------------------------------------------------------

# per-dtype max-abs-error tolerance for attention outputs over O(1) values:
# bf16 = the unquantized pool (float32 in tests) — only kernel-vs-XLA float
# association noise; int8 ~ absmax/254 per row pre-softmax; fp8 ~ absmax/16
_TOL = {"bf16": 3e-5, "int8": 0.08, "fp8_e4m3": 0.4}

QUANT_CASES = [
    (pattern, arg, s, impl, kd)
    for pattern, arg, s in (
        ("dense", None, 128), ("window", 16, 128), ("butterfly", None, 512),
    )
    for impl in ("xla_chunked", "flash_kernel")
    for kd in ("bf16", "int8", "fp8_e4m3")
]


def _build_pool(k_full, v_full, page, kv_dtype):
    """Scatter exact (B, S, KV, hd) KV into a per-request-paged pool at
    ``kv_dtype`` through the real write path, returning the pool leaves and
    the identity page tables."""
    b, s, kv, hd = k_full.shape
    n_tiles = -(-s // page)
    n_pages = b * n_tiles
    store = quant.kv_store_dtype(kv_dtype, jnp.float32)
    pt = (
        jnp.arange(b, dtype=jnp.int32)[:, None] * n_tiles
        + jnp.arange(n_tiles, dtype=jnp.int32)[None, :]
    )
    rows = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
    valid = jnp.ones((b, s), bool)
    # one-layer stacks in the stored layout (read at layer 0)
    kp = jnp.zeros((1, kv, n_pages * page, hd), store)
    vp = jnp.zeros((1, kv, n_pages * page, hd), store)
    if kv_dtype == "bf16":
        kp = tf._paged_kv_write(kp, k_full, rows, valid, pt, page)
        vp = tf._paged_kv_write(vp, v_full, rows, valid, pt, page)
        return kp, vp, None, None, pt
    ks = jnp.zeros((1, kv, n_pages, 1, page), jnp.float32)
    vs = jnp.zeros((1, kv, n_pages, 1, page), jnp.float32)
    kp, ks = tf._paged_kv_write(kp, k_full, rows, valid, pt, page, scale=ks)
    vp, vs = tf._paged_kv_write(vp, v_full, rows, valid, pt, page, scale=vs)
    return kp, vp, ks, vs, pt


@pytest.mark.parametrize("pattern,arg,s,impl,kv_dtype", QUANT_CASES)
def test_paged_quant_matches_contiguous(pattern, arg, s, impl, kv_dtype):
    """Attention outputs through a quantized paged pool must sit within the
    dtype's tolerance of the contiguous (exact-KV) oracle on every execution
    form and mode — decode, chunk, and admission prefill; 4 query heads over
    2 kv heads (GQA)."""
    b, h, kv, hd = 2, 4, 2, 64
    spec = AttentionSpec(impl=impl, pattern=pattern, pattern_arg=arg)
    page = sparsity.pick_pattern_tiles(1, s, spec.q_tile, spec.kv_tile)[1]
    rt = Runtime()
    key = jax.random.PRNGKey(3)
    kk, kv_, kq, kc = jax.random.split(key, 4)
    k_full = jax.random.normal(kk, (b, s, kv, hd), jnp.float32)
    v_full = jax.random.normal(kv_, (b, s, kv, hd), jnp.float32)
    kp, vp, ks, vs, pt = _build_pool(k_full, v_full, page, kv_dtype)
    tol = _TOL[kv_dtype]

    # -- decode: per-row live lengths ------------------------------------
    q1 = jax.random.normal(kq, (b, h, hd), jnp.float32)
    cur = jnp.asarray([s, s - 37], jnp.int32)  # row 1 mid-tile frontier
    got = run_paged_decode_attention(
        q1, kp, vp, cur, pt, page=page, spec=spec, rt=rt,
        k_scale=ks, v_scale=vs,
    )
    ref = run_decode_attention(q1, k_full, v_full, cur, spec=spec, rt=rt)
    assert float(jnp.max(jnp.abs(got - ref))) <= tol, "decode"

    # -- chunk: mixed rows at their own frontiers ------------------------
    c = 8
    qc = jax.random.normal(kc, (b, c, h, hd), jnp.float32)
    start = jnp.asarray([s - c, s // 2], jnp.int32)
    ntok = jnp.asarray([c, c - 3], jnp.int32)
    got = run_paged_chunk_attention(
        qc, kp, vp, start, ntok, pt, page=page, spec=spec, rt=rt,
        k_scale=ks, v_scale=vs,
    )
    ref = run_chunk_attention(qc, k_full, v_full, start, ntok, spec=spec, rt=rt)
    assert float(jnp.max(jnp.abs(got - ref))) <= tol, "chunk"

    # -- admission prefill: batch-1 prompt over its own pages ------------
    qp = jax.random.normal(kq, (1, s, h, hd), jnp.float32)
    got = run_paged_prefill_attention(
        qp, k_full[:1], v_full[:1], kp, vp, pt[:1], page=page, spec=spec,
        rt=rt, k_scale=ks, v_scale=vs,
    )
    ref = run_attention(qp, k_full[:1], v_full[:1], spec=spec, causal=True, rt=rt)
    assert float(jnp.max(jnp.abs(got - ref))) <= tol, "prefill"


# --------------------------------------------------------------------------
# End-to-end engine: bf16 bit-identity, fused-vs-XLA agreement, shard parity
# --------------------------------------------------------------------------


def _serve(cfg, mesh, params, prompts, **kw):
    loop = ServeLoop(cfg, mesh, params, batch=2, cache_len=64, paged=True, **kw)
    out = loop.run([
        Request(uid=i, prompt=p, max_new=5) for i, p in enumerate(prompts)
    ])
    loop.close()
    assert loop.pool.in_use == 0
    return [r.generated for r in out]


def test_serve_kv_dtype_end_to_end():
    """Three engine-level contracts on one workload (GQA config):
    ``kv_dtype='bf16'`` is token-identical to the default paged engine (the
    PR-9 graph — no scale leaves exist to change it); the fused int8 path is
    token-identical to the XLA int8 path (both read the SAME quantized pool,
    so greedy argmax must agree); and host page sharding cannot change int8
    results (physical page ids are not part of the math)."""
    cfg = _f32(registry.get("qwen3-0.6b", reduced=True))
    mesh = make_local_mesh()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(11)
    prompts = [
        rng.integers(0, cfg.vocab, size=ln).astype(np.int32)
        for ln in (17, 3, 41)
    ]
    base = _serve(cfg, mesh, params, prompts)
    bf16 = _serve(cfg, mesh, params, prompts, kv_dtype="bf16")
    assert bf16 == base, "kv_dtype='bf16' must reproduce the default engine"

    i8_xla = _serve(cfg, mesh, params, prompts, kv_dtype="int8")
    i8_fused = _serve(
        cfg, mesh, params, prompts, kv_dtype="int8", attn_impl="flash_kernel"
    )
    assert i8_fused == i8_xla, "fused and XLA read the same quantized pool"

    i8_sharded = _serve(
        cfg, mesh, params, prompts, kv_dtype="int8", page_shards=2,
        pool_pages=16,
    )
    assert i8_sharded == i8_xla, "page sharding is invisible to the math"


def test_serve_quantized_rejects_contiguous():
    cfg = _f32(registry.get("qwen3-0.6b", reduced=True))
    with pytest.raises(ValueError, match="paged"):
        ServeLoop(
            cfg, make_local_mesh(), None, batch=1, cache_len=64,
            kv_dtype="int8",
        )
