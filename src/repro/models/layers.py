"""Shared layers: norms, RoPE, attention execution forms + spec dispatch.

Two attention execution forms live behind :class:`repro.core.attention.
AttentionSpec` (selected per model via ``ModelConfig.attention``):

* ``xla_chunked`` — :func:`chunked_attention` here: queries are processed in
  static *prefix chunks*, each attending exactly its causal KV prefix (plus a
  masked diagonal block).  This keeps compiled FLOPs within ~(1 + 1/n_chunks)
  of the causal optimum — important because the roofline terms are read off
  the compiled HLO — and bounds transient score memory to (chunk x prefix).
  Sliding windows (mixtral) drop whole out-of-window chunks statically.
  Score matrices still round-trip HBM: this is the paper's Fig. 2 baseline.
* ``flash_kernel`` — the fused Pallas online-softmax kernel
  (:mod:`repro.kernels.flash_attention`): score tiles stay VMEM-resident.

:func:`run_attention` / :func:`run_decode_attention` are the dispatchers the
model runtime calls.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import quant, sparsity
from repro.core.attention import AttentionSpec, truncate_kv_live
from repro.distributed.sharding import constrain

__all__ = [
    "Runtime",
    "rms_norm",
    "layer_norm",
    "apply_rope",
    "chunked_attention",
    "decode_attention",
    "chunk_attention_cache",
    "run_attention",
    "run_decode_attention",
    "run_chunk_attention",
    "gather_pages",
    "run_paged_prefill_attention",
    "run_paged_decode_attention",
    "run_paged_chunk_attention",
    "silu",
    "gelu",
]


@dataclasses.dataclass(frozen=True)
class Runtime:
    """Per-run execution context: mesh + resolved parallelism modes."""

    mesh: Mesh | None = None
    attn_mode: str = "tp"  # tp (head-sharded) | cp (sequence-sharded)
    moe_mode: str = "ep"  # ep | tp
    rules: dict | None = None  # sharding-rule override (pure_dp lever)


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * (1.0 + w.astype(x.dtype))


def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(x.dtype) * (1.0 + w.astype(x.dtype)) + b.astype(x.dtype)


def silu(x):
    return x * jax.nn.sigmoid(x)


def gelu(x):
    return jax.nn.gelu(x, approximate=True)


def _rope_angles(positions: jax.Array, head_dim: int, theta: float) -> tuple:
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, half)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(
    x: jax.Array, positions: jax.Array, theta: float = 1e4
) -> jax.Array:
    """x: (B, S, H, hd); positions: (S,) or (B, S)."""
    hd = x.shape[-1]
    cos, sin = _rope_angles(positions, hd, theta)
    if cos.ndim == 2:  # (S, half) -> broadcast batch/heads
        cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    else:  # (B, S, half)
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    y = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return y.astype(x.dtype)


def _q_axes(rt: Runtime, chunk_len: int, heads: int):
    tp = 1
    if rt.mesh is not None and "model" in rt.mesh.axis_names:
        tp = dict(zip(rt.mesh.axis_names, rt.mesh.devices.shape))["model"]
    if rt.attn_mode == "tp" and heads % max(tp, 1) == 0:
        return ("batch", None, "tp", None)
    if chunk_len % max(tp, 1) == 0:
        return ("batch", "seq", None, None)
    return ("batch", None, None, None)


def chunked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    chunk: int = 2048,
    rt: Runtime = Runtime(),
    f32_softmax: bool = True,
    pattern_mask: np.ndarray | None = None,
) -> jax.Array:
    """Prefix-chunked attention (the ``xla_chunked`` reference form).
    q: (B, S, H, hd); k, v: (B, S, KV, hd).

    ``pattern_mask`` is the static (S_q, S_kv) token-level expansion of a
    block-sparsity map — *mask-only* on this backend: dead blocks are still
    computed and round-tripped through HBM, which is exactly the paper's
    point about sparsity without dataflow orchestration."""
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    scale = 1.0 / math.sqrt(hd)
    chunk = min(chunk, s)
    # non-divisible S (prime lengths included): pad up to a chunk multiple and
    # mask the tail — NOT gcd(s, chunk), which degenerates to chunk=1 and
    # statically unrolls s chunks
    s_pad = -(-s // chunk) * chunk
    n_chunks = s_pad // chunk
    padded = s_pad != s

    q = constrain(q, _q_axes(rt, s, h), rt.mesh, rt.rules)
    # KV must stay seq-local: a seq-sharded KV would force the SPMD partitioner
    # into full-replication copies at every chunk slice.  KV heads shard over
    # `model` when divisible, otherwise replicate (GQA KV replication).
    k = constrain(k, ("batch", None, "tp", None), rt.mesh, rt.rules)
    v = constrain(v, ("batch", None, "tp", None), rt.mesh, rt.rules)
    if padded:
        pad = [(0, 0), (0, s_pad - s), (0, 0), (0, 0)]
        q = jnp.pad(q, pad)
        if causal:  # self-attention: prefix slicing needs the padded length
            k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    qr = q.reshape(b, s_pad, kvh, g, hd)
    pm_full = None
    if pattern_mask is not None:
        # pad q rows True (sliced off at the end), kv cols False (dead tail)
        pm_full = np.ones((s_pad, k.shape[1]), bool)
        pm_full[:s, : pattern_mask.shape[1]] = pattern_mask
        pm_full[:s, pattern_mask.shape[1] :] = False
    outs = []
    for i in range(n_chunks):  # static unroll: exact per-chunk causal prefixes
        q_i = jax.lax.slice_in_dim(qr, i * chunk, (i + 1) * chunk, axis=1)
        end = (i + 1) * chunk if causal else k.shape[1]
        start = 0
        if window is not None and causal:
            # earliest key needed by the FIRST query row of this chunk
            start = max(0, i * chunk - window + 1)
            start = (start // chunk) * chunk  # align to chunk (conservative)
        k_i = jax.lax.slice_in_dim(k, start, end, axis=1)
        v_i = jax.lax.slice_in_dim(v, start, end, axis=1)
        scores = jnp.einsum(
            "bqkgd,bskd->bkgqs", q_i, k_i, preferred_element_type=jnp.float32
        ) * scale
        if not f32_softmax:  # §Perf lever: halve the score HBM traffic
            scores = scores.astype(q.dtype)
        neg = jnp.asarray(-1e30 if f32_softmax else -3e38, scores.dtype)
        if causal or window is not None or pattern_mask is not None:
            qpos = i * chunk + jnp.arange(chunk)
            kpos = start + jnp.arange(end - start)
            mask = jnp.ones((chunk, end - start), bool)
            if causal:
                mask &= qpos[:, None] >= kpos[None, :]
                if padded:
                    mask &= kpos[None, :] < s  # padded tail keys
            if window is not None:
                mask &= kpos[None, :] > qpos[:, None] - window
            if pm_full is not None:  # static numpy slice of the pattern mask
                mask &= jnp.asarray(pm_full[i * chunk : (i + 1) * chunk, start:end])
            scores = jnp.where(mask[None, None, None], scores, neg)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        out_i = jnp.einsum("bkgqs,bskd->bqkgd", probs, v_i)
        outs.append(out_i.reshape(b, chunk, h, hd))
    out = jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    return out[:, :s] if padded else out


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    cur_len: jax.Array | None = None,
    pattern_mask: jax.Array | None = None,
) -> jax.Array:
    """One-token attention over a (possibly sequence-sharded) KV cache.

    q: (B, H, hd); caches: (B, S, KV, hd).  ``cur_len`` masks unwritten cache
    rows: a scalar applies one live length batch-wide, a (B,) vector masks
    per request (ragged continuous batching).  ``pattern_mask`` (B, S) is the
    per-row token expansion of the block-sparsity map (mask-only on this
    backend).  Scores stay tiny, so plain einsum + softmax — XLA inserts the
    cross-shard max/sum reductions when the cache's S axis is sharded
    (flash-decode style combine).
    """
    b, h, hd = q.shape
    kvh = k_cache.shape[2]
    g = h // kvh
    scale = 1.0 / math.sqrt(hd)
    qr = q.reshape(b, kvh, g, hd)
    scores = jnp.einsum(
        "bkgd,bskd->bkgs", qr, k_cache, preferred_element_type=jnp.float32
    ) * scale
    if cur_len is not None:
        cl = jnp.asarray(cur_len, jnp.int32).reshape(-1, 1)  # scalar | (B, 1)
        mask = jnp.arange(k_cache.shape[1])[None, :] < cl  # (1|B, S)
        scores = jnp.where(mask[:, None, None, :], scores, -1e30)
    if pattern_mask is not None:
        scores = jnp.where(pattern_mask[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, v_cache)
    return out.reshape(b, h, hd)


def chunk_attention_cache(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    start: jax.Array,
    *,
    window: int | None = None,
    pattern_mask: jax.Array | None = None,
    kpos: jax.Array | None = None,
) -> jax.Array:
    """Chunk-of-queries attention over a shared KV cache with a per-row
    causal frontier (the XLA form of the mixed chunked-prefill step).

    q: (B, C, H, hd); caches: (B, S, KV, hd); ``start`` (B,) is the absolute
    position of each row's first query — query i attends cache keys at
    positions ``<= start[b] + i`` (its own position is the newest written
    row, so the frontier doubles as the written-cache mask).
    ``pattern_mask`` (B, C, S) is the per-query token expansion of the
    block-sparsity map (mask-only on this backend).  ``kpos`` (B, S)
    overrides the identity position map when the cache rows are NOT laid out
    at their absolute positions (the mod-window ring gathers slot-ordered
    pages; stale slots carry an out-of-frontier sentinel).  Rows beyond a
    row's valid count produce garbage the caller never reads."""
    b, c, h, hd = q.shape
    skv, kvh = k_cache.shape[1], k_cache.shape[2]
    g = h // kvh
    scale = 1.0 / math.sqrt(hd)
    qr = q.reshape(b, c, kvh, g, hd)
    scores = jnp.einsum(
        "bqkgd,bskd->bkgqs", qr, k_cache, preferred_element_type=jnp.float32
    ) * scale
    qpos = jnp.asarray(start, jnp.int32)[:, None] + jnp.arange(c, dtype=jnp.int32)
    if kpos is None:
        kpos = jnp.arange(skv, dtype=jnp.int32)[None, :]  # (1, S) identity
    kpos = jnp.asarray(kpos, jnp.int32)
    mask = kpos[:, None, :] <= qpos[:, :, None]  # (B, C, S) frontier
    if window is not None:
        mask &= kpos[:, None, :] > qpos[:, :, None] - window
    if pattern_mask is not None:
        mask &= pattern_mask
    scores = jnp.where(mask[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v_cache)
    return out.reshape(b, c, h, hd)


def _fused(spec: AttentionSpec, rt: Runtime) -> bool:
    """True when ``spec`` selects the fused kernel.  A ``pallas_call`` is a
    per-device kernel the SPMD partitioner cannot split, so a
    ``flash_kernel`` spec on a mesh of more than one device is refused — it
    is never swapped for the XLA form, which would read every dead tile."""
    if not spec.fused:
        return False
    if rt.mesh is not None and rt.mesh.devices.size > 1:
        raise ValueError(
            "attention impl 'flash_kernel' is one Pallas kernel per device "
            "and cannot be partitioned over the "
            f"{rt.mesh.devices.size}-device mesh {dict(rt.mesh.shape)}; ask "
            "for impl='xla_chunked' on a multi-device mesh"
        )
    return True


def run_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    spec: AttentionSpec = AttentionSpec(),
    causal: bool = True,
    window: int | None = None,
    rt: Runtime = Runtime(),
) -> jax.Array:
    """Execute train/prefill attention under the configured spec.

    ``spec.pattern`` applies to both forms: the fused kernel iterates only
    live blocks (grid-level skipping); the chunked form masks with the same
    map's token expansion (mask-only — the parity target, and the form a
    multi-device mesh must ask for)."""
    if _fused(spec, rt):
        from repro.kernels import ops  # local import: kernels are optional

        return ops.flash_attention(q, k, v, causal=causal, window=window, spec=spec)
    pattern, arg, causal, window = sparsity.canonical_pattern(
        spec.pattern, spec.pattern_arg, causal, window
    )
    pmask = None
    if pattern != "dense":
        tq, tk = sparsity.pick_pattern_tiles(
            q.shape[1], k.shape[1], spec.q_tile, spec.kv_tile
        )
        bm = sparsity.build_block_map(
            pattern, q.shape[1], k.shape[1], tq, tk, causal=causal,
            window=window, pattern_arg=arg,
        )
        pmask = sparsity.token_mask(bm)
    return chunked_attention(
        q, k, v, causal=causal, window=window, chunk=spec.chunk, rt=rt,
        f32_softmax=spec.f32_softmax, pattern_mask=pmask,
    )


def run_decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    cur_len: jax.Array | None = None,
    *,
    spec: AttentionSpec = AttentionSpec(),
    rt: Runtime = Runtime(),
    kv_live: int | None = None,
) -> jax.Array:
    """Execute one-token cache attention under the configured spec.

    ``cur_len``: None (whole cache live), scalar (batch-wide live length), or
    (B,) per-request live lengths (ragged continuous batching).  ``kv_live``
    is a static host-known upper bound on every row's live length (the serve
    engine's bucketed ``max(pos)+1``): both forms read only the first
    ``kv_live`` cache rows instead of streaming the padded cache.
    ``spec.pattern`` restricts each row to its own live kv tiles."""
    if _fused(spec, rt):
        from repro.kernels import ops

        return ops.flash_decode(
            q, k_cache, v_cache, cur_len, spec=spec, kv_live=kv_live
        )
    k_cache, v_cache, skv = truncate_kv_live(k_cache, v_cache, kv_live)
    pattern, arg, _, window = sparsity.canonical_pattern(
        spec.pattern, spec.pattern_arg, True, None
    )
    pmask = None
    if pattern != "dense" or window is not None:
        _, tk = sparsity.pick_pattern_tiles(1, skv, spec.q_tile, spec.kv_tile)
        if cur_len is None:
            cl = jnp.full((q.shape[0],), skv, jnp.int32)
        else:
            cl = jnp.broadcast_to(
                jnp.asarray(cur_len, jnp.int32).reshape(-1), (q.shape[0],)
            )
        pmask = sparsity.decode_token_mask(
            pattern, cl, skv, spec.q_tile, tk, window=window, pattern_arg=arg
        )
        if window is not None:  # fine window edge (matches the prefill mask)
            pmask &= jnp.arange(skv)[None, :] > cl[:, None] - 1 - window
    return decode_attention(q, k_cache, v_cache, cur_len, pattern_mask=pmask)


# --------------------------------------------------------------------------
# Paged cache dispatch: the fused kernels stream the pool through translated
# physical-page tables; the XLA forms gather the virtual cache back from the
# pool and run the SAME masked forms — parity with the contiguous engine by
# construction (one liveness map, two address spaces).
# --------------------------------------------------------------------------


def gather_pages(
    pool: jax.Array, page_table: jax.Array, n_rows: int, page: int,
    page_range: tuple[int, int] | None = None,
    layer: jax.Array | int = 0,
) -> jax.Array:
    """Materialise rows ``0..n_rows-1`` of each request's VIRTUAL cache from
    layer ``layer`` of the shared page pool, read where it is stored: only
    the gathered rows move, no layer is sliced out first.  pool: a stacked
    pool leaf, (L, KV, n_pages * page, hd) K/V -> (B, n_rows, KV, hd), or a
    quantized pool's scale leaf (L, KV, n_pages, 1, page) -> (B, n_rows, KV);
    page_table: (B, n_vtiles) physical page ids (sentinel ``n_pages`` =
    unallocated).  Unallocated tiles gather clamped garbage — every
    consumer masks them (causal frontier / cur_len / pattern), exactly as the
    contiguous engine masks its unwritten rows.

    Aliasing is transparent here: with the radix prefix cache, SEVERAL rows'
    tables (and several virtual tiles, in principle) may name the same
    physical page — a pure read-side gather returns each row its own view of
    the shared rows, bit-identical to a private copy, so the XLA forms need
    no CoW awareness (the host engine forks pages before any write).

    ``page_range=(lo, hi)`` makes the gather MESH-LOCAL: ``pool`` is then ONE
    shard of a page-sharded pool holding physical pages ``lo..hi-1``
    (``(hi - lo) * page`` rows), ids rebase to the shard, and rows whose page
    the shard does not own come back ZERO — each allocated tile is owned by
    exactly one shard, so a sum over the shards' gathers reassembles the
    replicated gather on every allocated row (a ``psum`` inside
    ``shard_map``, a plain sum in the host-side sweep test)."""
    scale_leaf = pool.ndim == 5
    n_pages = pool.shape[2] if scale_leaf else pool.shape[2] // page
    rows = jnp.arange(n_rows, dtype=jnp.int32)
    off = rows % page
    phys = page_table[:, rows // page]  # (B, n_rows) global ids
    if page_range is not None:
        lo, hi = page_range
        owned = (phys >= lo) & (phys < hi)
        phys = jnp.clip(phys - lo, 0, hi - lo - 1)
    else:
        phys = jnp.clip(phys, 0, n_pages - 1)
    # one index per (row, kv head), each reading one row of hd (one lane row
    # of scales), as the write scatters them: no backend has to move the
    # pool's axes to gather from it
    heads = jnp.arange(pool.shape[1], dtype=jnp.int32)
    if scale_leaf:
        out = pool[layer, heads, phys[..., None], 0, off[..., None]]
    else:
        out = pool[layer, heads, (phys * page + off)[..., None]]
    if page_range is None:
        return out
    # broadcast `owned` over the trailing dims — (KV, hd) for a K/V leaf,
    # (KV,) for a scale leaf
    owned = owned.reshape(owned.shape + (1,) * (out.ndim - 2))
    return jnp.where(owned, out, jnp.zeros((), out.dtype))


def ring_kpos(frontier: jax.Array, page: int, ring_tiles: int) -> jax.Array:
    """Absolute token position of every SLOT-ORDERED ring cache row.

    A mod-window gather (``gather_pages`` over a ``ring_tiles``-slot table)
    returns rows in slot order, not position order; this is the matching
    (B, ring_tiles * page) position map: slot s's r-th row is
    ``slot_tile(s) * page + r`` (the lap :func:`repro.core.sparsity.
    ring_slot_tiles` resolves from the frontier), and never-written slots
    carry a large sentinel every causal/frontier mask rejects."""
    st = sparsity.ring_slot_tiles(frontier, page, ring_tiles)  # (B, R)
    base = jnp.where(st >= 0, st * page, jnp.int32(1 << 30))
    off = jnp.arange(page, dtype=jnp.int32)
    return (base[:, :, None] + off[None, None, :]).reshape(st.shape[0], -1)


def _gather_dequant(
    q, k_pool, v_pool, k_scale, v_scale, page_table, n_rows, page, layer
):
    """Gather both pools' virtual rows of layer ``layer`` and, for a
    quantized pool, the matching scale rows — reconstructing the bf16 cache
    the contiguous (oracle) forms consume.  The scale leaves ride the SAME
    page table, so a CoW-forked, radix-aliased, or ring-phased page always
    lands next to its own scales."""
    kg = gather_pages(k_pool, page_table, n_rows, page, layer=layer)
    vg = gather_pages(v_pool, page_table, n_rows, page, layer=layer)
    if k_scale is not None:
        ks = gather_pages(k_scale, page_table, n_rows, page, layer=layer)
        vs = gather_pages(v_scale, page_table, n_rows, page, layer=layer)
        kg = quant.dequantize_rows(kg, ks, dtype=q.dtype)
        vg = quant.dequantize_rows(vg, vs, dtype=q.dtype)
    return kg, vg


def run_paged_prefill_attention(
    q: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    page_table: jax.Array,
    *,
    page: int,
    layer: jax.Array | int = 0,
    spec: AttentionSpec = AttentionSpec(),
    rt: Runtime = Runtime(),
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Admission prefill over a paged cache: q/k_new/v_new are the (1, S)
    prompt's projections (already scattered into layer ``layer`` of the
    stacked pools (L, KV, n_pages * page, hd) by the caller).
    The fused kernel reads the KV back *through the page table* — the
    physical-page indexing proof for the prefill grid; the XLA form attends
    the in-flight projections directly (the gather would reproduce them, and
    for a QUANTIZED pool the in-flight values are the exact pre-quantization
    KV — no dequant needed)."""
    if _fused(spec, rt):
        from repro.kernels import ops

        return ops.flash_paged_prefill(
            q, k_pool, v_pool, page_table, page=page, layer=layer, spec=spec,
            k_scale=k_scale, v_scale=v_scale,
        )
    return run_attention(q, k_new, v_new, spec=spec, causal=True, rt=rt)


def run_paged_decode_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    cur_len: jax.Array,
    page_table: jax.Array,
    *,
    page: int,
    layer: jax.Array | int = 0,
    spec: AttentionSpec = AttentionSpec(),
    rt: Runtime = Runtime(),
    kv_live: int | None = None,
    ring_window: int | None = None,
    ring_tiles: int | None = None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """One-token attention over layer ``layer`` of the stacked paged pools
    (L, KV, n_pages * page, hd), read where they are stored: q (B, H, hd),
    per-row ``cur_len`` live lengths in virtual token space.  ``kv_live``
    buckets the virtual extent (compile-per-bucket, like the contiguous
    engine).
    ``ring_window`` / ``ring_tiles`` select the mod-window ring form:
    positions are unbounded, the table's ``ring_tiles`` slots are reused in
    phase, and only the trailing ``ring_window`` keys are live.
    ``k_scale`` / ``v_scale`` carry a quantized pool's per-row dequant
    scales: the fused kernel applies them to the score tile, the XLA forms
    right after the gather — one scheme, two address spaces."""
    if _fused(spec, rt):
        from repro.kernels import ops

        return ops.flash_paged_decode(
            q, k_pool, v_pool, cur_len, page_table, page=page, layer=layer,
            spec=spec,
            kv_live=kv_live, ring_window=ring_window, ring_tiles=ring_tiles,
            k_scale=k_scale, v_scale=v_scale,
        )
    if ring_tiles is not None:
        cl = jnp.broadcast_to(
            jnp.asarray(cur_len, jnp.int32).reshape(-1), (q.shape[0],)
        )
        kg, vg = _gather_dequant(
            q, k_pool, v_pool, k_scale, v_scale, page_table,
            ring_tiles * page, page, layer,
        )
        kpos = ring_kpos(cl - 1, page, ring_tiles)  # (B, R*page) slot order
        mask = (kpos < cl[:, None]) & (kpos > (cl[:, None] - 1 - ring_window))
        return decode_attention(q, kg, vg, None, pattern_mask=mask)
    n_rows = page_table.shape[1] * page
    if kv_live is not None:
        n_rows = min(n_rows, max(int(kv_live), 1))
    kg, vg = _gather_dequant(
        q, k_pool, v_pool, k_scale, v_scale, page_table, n_rows, page, layer
    )
    return run_decode_attention(q, kg, vg, cur_len, spec=spec, rt=rt)


def run_paged_chunk_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    start: jax.Array,
    ntok: jax.Array,
    page_table: jax.Array,
    *,
    page: int,
    layer: jax.Array | int = 0,
    spec: AttentionSpec = AttentionSpec(),
    rt: Runtime = Runtime(),
    kv_live: int | None = None,
    ring_window: int | None = None,
    ring_tiles: int | None = None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Mixed chunked-prefill attention over layer ``layer`` of the stacked
    paged pools (the paged form of :func:`run_chunk_attention`), read where
    they are stored: q (B, C, H, hd) rows at absolute positions
    ``start[b]..``, per-row page tables, per-row live-tile tables translated
    to physical pages.  ``ring_window`` / ``ring_tiles`` select the
    mod-window ring form (slot-phase tables, absolute-position masks).
    ``k_scale`` / ``v_scale``: quantized-pool dequant scales (fused:
    on the score tile in-kernel; XLA: post-gather)."""
    if _fused(spec, rt):
        from repro.kernels import ops

        return ops.flash_paged_chunk(
            q, k_pool, v_pool, start, ntok, page_table, page=page, layer=layer,
            spec=spec,
            kv_live=kv_live, ring_window=ring_window, ring_tiles=ring_tiles,
            k_scale=k_scale, v_scale=v_scale,
        )
    if ring_tiles is not None:
        sv = jnp.asarray(start, jnp.int32).reshape(-1)
        nv = jnp.asarray(ntok, jnp.int32).reshape(-1)
        fr = sv + jnp.maximum(nv, 1) - 1  # per-row write frontier
        kg, vg = _gather_dequant(
            q, k_pool, v_pool, k_scale, v_scale, page_table,
            ring_tiles * page, page, layer,
        )
        kpos = ring_kpos(fr, page, ring_tiles)
        return chunk_attention_cache(
            q, kg, vg, sv, window=ring_window, kpos=kpos
        )
    n_rows = page_table.shape[1] * page
    if kv_live is not None:
        n_rows = min(n_rows, max(int(kv_live), 1))
    kg, vg = _gather_dequant(
        q, k_pool, v_pool, k_scale, v_scale, page_table, n_rows, page, layer
    )
    return run_chunk_attention(q, kg, vg, start, ntok, spec=spec, rt=rt)


def run_chunk_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    start: jax.Array,
    ntok: jax.Array,
    *,
    spec: AttentionSpec = AttentionSpec(),
    rt: Runtime = Runtime(),
    kv_live: int | None = None,
) -> jax.Array:
    """Execute one mixed chunked-prefill attention step under the configured
    spec: q (B, C, H, hd) chunk queries at absolute positions
    ``start[b]..start[b]+C-1`` over the shared cache, per-row causal frontier.

    The fused kernel streams each row's own live kv-tile table
    (:func:`repro.core.sparsity.chunk_live_tables` — traced from
    ``start + ntok``); the XLA form masks with the same map's per-query token
    expansion.  ``kv_live`` is the engine's bucketed static bound on the
    hottest row's frontier — both forms read only that cache prefix."""
    if _fused(spec, rt):
        from repro.kernels import ops

        return ops.flash_chunk(
            q, k_cache, v_cache, start, ntok, spec=spec, kv_live=kv_live
        )
    k_cache, v_cache, skv = truncate_kv_live(k_cache, v_cache, kv_live)
    pattern, arg, _, window = sparsity.canonical_pattern(
        spec.pattern, spec.pattern_arg, True, None
    )
    pmask = None
    if pattern != "dense":
        _, tk = sparsity.pick_pattern_tiles(1, skv, spec.q_tile, spec.kv_tile)
        qpos = jnp.asarray(start, jnp.int32)[:, None] + jnp.arange(
            q.shape[1], dtype=jnp.int32
        )
        pmask = sparsity.chunk_token_mask(
            pattern, qpos, skv, spec.q_tile, tk, window=window, pattern_arg=arg
        )
    return chunk_attention_cache(
        q, k_cache, v_cache, start, window=window, pattern_mask=pmask
    )
