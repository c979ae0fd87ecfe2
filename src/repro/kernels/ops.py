"""Jit'd dispatch wrappers: model-facing entry points for the Pallas kernels.

On the CPU host (this container) kernels run in ``interpret=True`` mode; on a
real TPU backend they compile through Mosaic.  The wrappers own padding,
layout flattening, and the multi-stage recursion that chains kernel calls for
transforms larger than one fused two-stage tile.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sparsity, stage_division as sd
from repro.core.attention import AttentionSpec, truncate_kv_live
from repro.kernels import fft2d, flash_attention as fa, monarch_bpmm

__all__ = [
    "monarch_linear",
    "dft_1d",
    "fnet_mixing_kernel",
    "flash_attention",
    "flash_chunk",
    "flash_decode",
    "flash_paged_prefill",
    "flash_paged_chunk",
    "flash_paged_decode",
]


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _pad_axis(x: jax.Array, axis: int, to: int) -> jax.Array:
    pad = to - x.shape[axis]
    if pad == 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg)


def monarch_linear(params, spec, x: jax.Array) -> jax.Array:
    """Fused-kernel execution of a (possibly sliced) monarch linear layer.

    Same contract as ``repro.core.api._apply_monarch`` — used when
    ``spec.impl == "monarch_kernel"``.
    """
    sp = spec.slices
    r, l = params["r"], params["l"]
    gout, gin, nb, b, _ = r.shape
    lead = x.shape[:-1]
    t = int(np.prod(lead)) if lead else 1
    xf = _pad_axis(x.reshape(t, x.shape[-1]), -1, sp.din_pad)
    xf = xf.reshape(t, gin, nb, b)

    # tile budget from the ACTUAL activation dtype: bf16 tiles are half the
    # bytes of f32, so they fit twice the tokens in the same VMEM budget
    tile = monarch_bpmm.pick_token_tile(
        gin, nb, b, dtype_bytes=jnp.dtype(x.dtype).itemsize
    )
    tpad = -(-t // tile) * tile
    xf = _pad_axis(xf, 0, tpad)
    y = monarch_bpmm.monarch_bpmm(
        xf, r.astype(x.dtype), l.astype(x.dtype), token_tile=tile, interpret=_interpret()
    )
    y = y[:t].reshape(t, sp.dout_pad)[:, : sp.dout]
    return y.reshape(*lead, sp.dout)


def dft_1d(
    xr: jax.Array,
    xi: jax.Array | None = None,
    plan: tuple[int, ...] | None = None,
    max_radix: int = sd.MAX_RADIX_COMPLEX,
) -> tuple[jax.Array, jax.Array]:
    """DFT along the last axis, chaining fused two-stage kernel calls per the
    multi-stage division plan (paper §V-B: a 64K transform = two 256-point
    kernel stages swapped through HBM — here the >2-stage tail recurses)."""
    n = xr.shape[-1]
    plan = tuple(plan) if plan else sd.plan_stages(n, max_radix)
    assert int(np.prod(plan)) == n

    lead = xr.shape[:-1]
    t = int(np.prod(lead)) if lead else 1
    xr2 = xr.reshape(t, n)
    xi2 = None if xi is None else xi.reshape(t, n)

    yr, yi = _dft_rec(xr2, xi2, plan)
    return yr.reshape(*lead, n), yi.reshape(*lead, n)


def _dft_rec(xr, xi, plan):
    t, n = xr.shape
    if len(plan) <= 2:
        n1, n2 = (plan[0], 1) if len(plan) == 1 else plan
        if n2 == 1:  # single dense stage
            w = np.asarray(sd.dft_matrix(n))
            wr, wi = jnp.asarray(w.real), jnp.asarray(w.imag)
            if xi is None:
                return xr @ wr, xr @ wi
            return xr @ wr - xi @ wi, xr @ wi + xi @ wr
        tile = fft2d.pick_token_tile(n, xi is not None)
        tpad = -(-t // tile) * tile
        xr_p = _pad_axis(xr, 0, tpad)
        xi_p = None if xi is None else _pad_axis(xi, 0, tpad)
        yr, yi = fft2d.dft_two_stage(
            xr_p, xi_p, n1=n1, n2=n2, token_tile=tile, interpret=_interpret()
        )
        return yr[:t], yi[:t]

    # outer stage n1 in XLA, inner (tail) stages through the fused kernel
    n1, ntail = plan[0], n // plan[0]
    xr_r = xr.reshape(t, n1, ntail)
    xi_r = None if xi is None else xi.reshape(t, n1, ntail)
    w = np.asarray(sd.dft_matrix(n1))
    wr, wi = jnp.asarray(w.real), jnp.asarray(w.imag)
    # contract n1:  a[t, k1, m] = sum_n x[t, n, m] W[n, k1]
    if xi_r is None:
        ar = jnp.einsum("tnm,nk->tkm", xr_r, wr)
        ai = jnp.einsum("tnm,nk->tkm", xr_r, wi)
    else:
        ar = jnp.einsum("tnm,nk->tkm", xr_r, wr) - jnp.einsum("tnm,nk->tkm", xi_r, wi)
        ai = jnp.einsum("tnm,nk->tkm", xr_r, wi) + jnp.einsum("tnm,nk->tkm", xi_r, wr)
    tw = np.asarray(sd.twiddle(n1, ntail))
    twr, twi = jnp.asarray(tw.real), jnp.asarray(tw.imag)
    br = ar * twr - ai * twi
    bi = ar * twi + ai * twr
    cr, ci = _dft_rec(br.reshape(t * n1, ntail), bi.reshape(t * n1, ntail), plan[1:])
    cr = jnp.swapaxes(cr.reshape(t, n1, ntail), 1, 2).reshape(t, n)
    ci = jnp.swapaxes(ci.reshape(t, n1, ntail), 1, 2).reshape(t, n)
    return cr, ci


# --------------------------------------------------------------------------
# Fused flash attention (AttentionSpec.impl == "flash_kernel")
# --------------------------------------------------------------------------

_LANES = 128


def _round_up(n: int, to: int) -> int:
    return -(-n // to) * to


canonical_pattern = sparsity.canonical_pattern


def _flash_prefill_raw(
    q: jax.Array, k: jax.Array, v: jax.Array,
    causal: bool, window: int | None, q_tile: int, kv_tile: int,
    pattern: str, pattern_arg: int | None,
) -> jax.Array:
    """Layout + padding around the Pallas prefill kernel.

    q: (B, S, H, hd); k, v: (B, Skv, KV, hd) -> (B, S, H, hd).  Head dim pads
    to the 128-lane boundary, sequences pad to the tile grid; padded keys are
    masked inside the kernel, padded query rows are sliced off here.  The
    static block map (pattern liveness + causal/window feasibility) becomes
    the kernel's packed kv-tile index map — dead tiles never enter the grid."""
    b, s, h, hd = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    tq, tk = fa.pick_tiles(s, skv, q_tile, kv_tile)
    sq_pad, skv_pad = _round_up(s, tq), _round_up(skv, tk)
    d = _round_up(hd, _LANES)

    bm = sparsity.build_block_map(
        pattern, s, skv, tq, tk, causal=causal, window=window,
        pattern_arg=pattern_arg,
    )

    qt = q.reshape(b, s, kvh, g, hd).transpose(0, 2, 3, 1, 4).reshape(b * kvh, g, s, hd)
    qt = jnp.pad(qt, ((0, 0), (0, 0), (0, sq_pad - s), (0, d - hd)))
    kt = k.transpose(0, 2, 1, 3).reshape(b * kvh, skv, hd)
    vt = v.transpose(0, 2, 1, 3).reshape(b * kvh, skv, hd)
    kt = jnp.pad(kt, ((0, 0), (0, skv_pad - skv), (0, d - hd)))
    vt = jnp.pad(vt, ((0, 0), (0, skv_pad - skv), (0, d - hd)))

    y = fa.mha_prefill(
        qt, kt[None], vt[None], 0, jnp.asarray(bm.kv_index),
        jnp.asarray(bm.step_live),
        scale=1.0 / math.sqrt(hd), causal=causal, window=window,
        s_q=s, s_kv=skv, q_tile=tq, kv_tile=tk, interpret=_interpret(),
    )
    y = y[:, :, :s, :hd].reshape(b, kvh, g, s, hd)
    return y.transpose(0, 3, 1, 2, 4).reshape(b, s, h, hd)


# The kernel has no Pallas backward; training falls back to differentiating
# the chunked XLA form (recompute — cheap next to the fwd save of score
# traffic, and transient score memory stays bounded to (chunk x prefix),
# unlike the naive full-score oracle).  Pattern-sparse forms differentiate
# the masked dense oracle under the same token mask.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_prefill(q, k, v, causal, window, q_tile, kv_tile, pattern, pattern_arg):
    return _flash_prefill_raw(q, k, v, causal, window, q_tile, kv_tile, pattern, pattern_arg)


def _flash_prefill_fwd(q, k, v, causal, window, q_tile, kv_tile, pattern, pattern_arg):
    y = _flash_prefill_raw(q, k, v, causal, window, q_tile, kv_tile, pattern, pattern_arg)
    return y, (q, k, v)


def _flash_prefill_bwd(causal, window, q_tile, kv_tile, pattern, pattern_arg, res, g):
    # local import: avoids a module-load cycle (models.layers imports this
    # module lazily from inside run_attention)
    from repro.models.layers import chunked_attention

    q, k, v = res
    pmask = None
    if pattern != "dense":
        tq, tk = fa.pick_tiles(q.shape[1], k.shape[1], q_tile, kv_tile)
        bm = sparsity.build_block_map(
            pattern, q.shape[1], k.shape[1], tq, tk, causal=causal,
            window=window, pattern_arg=pattern_arg,
        )
        pmask = sparsity.token_mask(bm)
    # chunked (not the naive oracle): transient score memory stays bounded to
    # (chunk x prefix) — the full-score vjp residual is S^2 per head, OOM in
    # exactly the long-context regime sparse patterns target
    _, vjp = jax.vjp(
        lambda q, k, v: chunked_attention(
            q, k, v, causal=causal, window=window, pattern_mask=pmask
        ),
        q, k, v,
    )
    return vjp(g)


_flash_prefill.defvjp(_flash_prefill_fwd, _flash_prefill_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    spec: AttentionSpec | None = None,
) -> jax.Array:
    """Fused online-softmax attention.  Same contract as
    ``repro.models.layers.chunked_attention`` (q: (B, S, H, hd); k, v:
    (B, Skv, KV, hd)) — used when ``AttentionSpec.impl == "flash_kernel"``.
    ``spec.pattern`` selects the block-sparsity map the kernel grid iterates."""
    spec = spec or AttentionSpec(impl="flash_kernel")
    pattern, arg, causal, window = canonical_pattern(
        spec.pattern, spec.pattern_arg, causal, window
    )
    return _flash_prefill(q, k, v, causal, window, spec.q_tile, spec.kv_tile, pattern, arg)


def flash_chunk(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    start: jax.Array,
    ntok: jax.Array,
    *,
    spec: AttentionSpec | None = None,
    kv_live: int | None = None,
) -> jax.Array:
    """Mixed chunked-prefill attention over the shared KV cache.

    q: (B, C, H, hd) — row b's chunk queries at absolute positions
    ``start[b] .. start[b]+C-1``; caches: (B, Skv, KV, hd); ``ntok`` (B,) is
    each row's valid-token count (0 = idle, 1 = decode, >1 = prompt chunk).
    Returns (B, C, H, hd); rows ``i >= ntok[b]`` are garbage the caller never
    reads (the engine gathers logits at ``ntok-1``).

    One kernel serves every row mode: the per-row live kv-tile table
    (:func:`repro.core.sparsity.chunk_live_tables`) is traced data built from
    each row's causal frontier ``start + ntok``, so a decode row streams
    exactly its written (pattern-live) tiles while a mid-prompt row streams
    its chunk's — the grid never visits a dead tile for either."""
    spec = spec or AttentionSpec(impl="flash_kernel")
    pattern, arg, _, window = canonical_pattern(
        spec.pattern, spec.pattern_arg, True, None
    )
    b, c, h, hd = q.shape
    kvh = k_cache.shape[2]
    k_cache, v_cache, skv = truncate_kv_live(k_cache, v_cache, kv_live)
    g = h // kvh
    _, tk = fa.pick_tiles(1, skv, spec.q_tile, spec.kv_tile)
    skv_pad = _round_up(skv, tk)
    d = _round_up(hd, _LANES)
    cp = _round_up(c, 8)

    qt = q.reshape(b, c, kvh, g, hd).transpose(0, 2, 3, 1, 4).reshape(b * kvh, g, c, hd)
    qt = jnp.pad(qt, ((0, 0), (0, 0), (0, cp - c), (0, d - hd)))
    kt = k_cache.transpose(0, 2, 1, 3).reshape(b * kvh, skv, hd)
    vt = v_cache.transpose(0, 2, 1, 3).reshape(b * kvh, skv, hd)
    kt = jnp.pad(kt, ((0, 0), (0, skv_pad - skv), (0, d - hd)))
    vt = jnp.pad(vt, ((0, 0), (0, skv_pad - skv), (0, d - hd)))

    start = jnp.asarray(start, jnp.int32).reshape(-1)
    kv_index, step_live = sparsity.chunk_live_tables(
        pattern, start, ntok, c, skv_pad, spec.q_tile, tk,
        window=window, pattern_arg=arg,
    )
    kv_index = jnp.repeat(kv_index, kvh, axis=0)  # (B*KV, max_live)
    step_live = jnp.repeat(step_live, kvh, axis=0)
    start_rows = jnp.repeat(start, kvh)

    y = fa.mha_chunk(
        qt, kt, vt, start_rows, kv_index, step_live,
        scale=1.0 / math.sqrt(hd), window=window, s_kv=skv,
        q_tile=spec.q_tile, kv_tile=tk, pattern=pattern, pattern_arg=arg,
        interpret=_interpret(),
    )
    y = y[:, :, :c, :hd].reshape(b, kvh, g, c, hd)
    return y.transpose(0, 3, 1, 2, 4).reshape(b, c, h, hd)


def flash_decode(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    cur_len: jax.Array | None = None,
    *,
    spec: AttentionSpec | None = None,
    kv_live: int | None = None,
) -> jax.Array:
    """Flash-decode over a KV cache: partial max/sum-exp combine across kv
    tiles in VMEM.  q: (B, H, hd); caches: (B, S, KV, hd) -> (B, H, hd).
    ``cur_len`` masks cache rows not yet written: a traced scalar applies one
    length to the whole batch, a (B,) vector gives every request its own live
    length (ragged continuous batching).

    True tile skipping, two mechanisms:
    * ``kv_live`` (static, host-known bound on every row's live length — the
      serve engine's bucketed ``max(pos)+1``) truncates the streamed cache to
      its first ``kv_live`` rows before the kernel: a 128-token request on a
      16k cache reads 1 kv tile, not 128.
    * ``spec.pattern`` builds a *per-row* live kv-tile table from ``cur_len``
      (the decoding token's pattern row), so the grid's kv extent is the
      pattern's static worst case (O(log n) tiles for butterfly) and each row
      visits only its own live tiles."""
    spec = spec or AttentionSpec(impl="flash_kernel")
    pattern, arg, _, window = canonical_pattern(
        spec.pattern, spec.pattern_arg, True, None
    )
    b, h, hd = q.shape
    kvh = k_cache.shape[2]
    # static truncation: rows beyond every request's live length are
    # sliced out of the stream entirely (the bias would only mask them)
    k_cache, v_cache, skv = truncate_kv_live(k_cache, v_cache, kv_live)
    g = h // kvh
    _, tk = fa.pick_tiles(1, skv, spec.q_tile, spec.kv_tile)
    skv_pad = _round_up(skv, tk)
    d = _round_up(hd, _LANES)
    gp = _round_up(g, 8)

    qt = jnp.pad(q.reshape(b, kvh, g, hd), ((0, 0), (0, 0), (0, gp - g), (0, d - hd)))
    qt = qt.reshape(b * kvh, gp, d)
    kt = k_cache.transpose(0, 2, 1, 3).reshape(b * kvh, skv, hd)
    vt = v_cache.transpose(0, 2, 1, 3).reshape(b * kvh, skv, hd)
    kt = jnp.pad(kt, ((0, 0), (0, skv_pad - skv), (0, d - hd)))
    vt = jnp.pad(vt, ((0, 0), (0, skv_pad - skv), (0, d - hd)))

    if cur_len is None:
        cl_rows = jnp.full((b,), skv, jnp.int32)
    else:  # scalar broadcasts; (B,) stays per-row
        cl_rows = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32).reshape(-1), (b,))
    kpos = jnp.arange(skv_pad)
    valid = (kpos[None, :] < skv) & (kpos[None, :] < cl_rows[:, None])  # (B, Skv_pad)
    if window is not None:  # fine window edge (matches the prefill mask)
        valid &= kpos[None, :] > cl_rows[:, None] - 1 - window
    bias = jnp.where(valid, 0.0, fa.NEG_INF).astype(jnp.float32)
    # one validity row per (batch, kv_head) grid row
    bias = jnp.broadcast_to(bias[:, None, :], (b, kvh, skv_pad)).reshape(
        b * kvh, skv_pad
    )

    # per-row live kv-tile tables: each request streams only the cache tiles
    # that are written AND pattern-live for its own position
    kv_index, step_live = sparsity.decode_live_tables(
        pattern, cl_rows, skv_pad, spec.q_tile, tk, window=window, pattern_arg=arg
    )
    kv_index = jnp.repeat(kv_index, kvh, axis=0)  # (B*KV, max_live)
    step_live = jnp.repeat(step_live, kvh, axis=0)

    y = fa.mha_decode(
        qt, kt, vt, bias, kv_index, step_live,
        scale=1.0 / math.sqrt(hd), kv_tile=tk, interpret=_interpret(),
    )
    return y.reshape(b, kvh, gp, d)[:, :, :g, :hd].reshape(b, h, hd)


# --------------------------------------------------------------------------
# Paged cache forms: the kernels stream a batch-shared page pool through the
# translated (physical-page) live tables — same grids, redirected DMA
# --------------------------------------------------------------------------


def _pool_dims(k_pool: jax.Array, page: int) -> tuple[int, int]:
    """(KV heads, pages) of a stacked pool leaf (L, KV, n_pages * page, hd),
    the layout the kernels read where it is stored.  The pool keeps the
    model's head width: the kernels' K/V block spans it whole (a block dim
    equal to the array's is legal at any width), so neither the pool nor the
    queries are padded."""
    _, kvh, rows, _ = k_pool.shape
    if rows % page:
        raise ValueError(f"pool rows {rows} not a page multiple ({page})")
    return kvh, rows // page


def _kernel_pools(layer, *leaves):
    """``(layer, leaves)`` as a paged kernel takes them.  Compiled for the
    TPU the kernel reads the stacked leaves where they are stored.  In
    interpret mode (the CPU) the grid runs as a while loop that carries
    every operand and writes its blocks back, so a stacked leaf passed whole
    would be copied on every call: it is handed the one layer it reads, as
    a one-layer stack read at layer 0."""
    if not _interpret():
        return layer, leaves
    layer = jnp.asarray(layer, jnp.int32)
    return 0, tuple(
        None if a is None else jax.lax.dynamic_slice_in_dim(a, layer, 1, 0)
        for a in leaves
    )


def _virtual_extent(page_table: jax.Array, page: int, kv_live: int | None) -> int:
    """Static virtual cache length the tables cover: the page table's full
    span, truncated to the engine's bucketed ``kv_live`` bound (rounded up to
    a whole page — tables are tile-granular)."""
    vl = page_table.shape[-1] * page
    if kv_live is not None:
        vl = min(vl, _round_up(max(int(kv_live), 1), page))
    return vl


def _local_pool_bound(page_range: tuple[int, int], n_local: int) -> int:
    """Sanity-check a mesh-local call: the pool passed in must be exactly the
    shard ``page_range`` names, and the translation's in-bounds check runs
    against ``hi`` (the sentinel is >= the global page count >= hi, so the
    ownership mask subsumes the allocated mask)."""
    lo, hi = page_range
    if hi - lo != n_local:
        raise ValueError(
            f"page_range {page_range} names {hi - lo} pages but the local "
            f"pool holds {n_local}"
        )
    return hi


def flash_paged_prefill(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    page_table: jax.Array,
    *,
    page: int,
    layer: jax.Array | int = 0,
    spec: AttentionSpec | None = None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Fused prefill attention reading prompt KV back through the page pool.

    q: (1, S, H, hd) — one admitted request's (bucketed) prompt, positions
    0..S-1; ``k_pool`` / ``v_pool``: (L, KV, n_pages * page, hd) the global
    pool as stored, stacked by layer, already holding this prompt's KV in
    layer ``layer`` (the model layer scatters before attention), which the
    kernel reads in place — a single-layer pool is passed as ``pool[None]``
    with layer 0; ``page_table``: (n_vtiles,) this request's virtual-tile ->
    physical-page map.  The static block map over the prompt translates to
    physical page ids, so the prefill grid streams pool pages directly —
    batch-1 because the table is shared across grid rows, which is exactly
    the admission engine's shape.  ``k_scale`` / ``v_scale`` ((L, KV,
    n_pages, 1, page) f32 or None) are a quantized pool's per-row dequant
    scales, stored beside it — forwarded through the same layer and page
    indirection."""
    spec = spec or AttentionSpec(impl="flash_kernel")
    pattern, arg, causal, window = canonical_pattern(
        spec.pattern, spec.pattern_arg, True, None
    )
    b, s, h, hd = q.shape
    if b != 1:
        raise ValueError(
            f"paged prefill is batch-1 (shared block map), got batch {b}"
        )
    kvh, n_pages = _pool_dims(k_pool, page)
    g = h // kvh
    tq, _ = fa.pick_tiles(s, s, spec.q_tile, spec.kv_tile)
    sq_pad = _round_up(s, tq)

    bm = sparsity.build_block_map(
        pattern, s, s, tq, page, causal=causal, window=window, pattern_arg=arg
    )
    kv_phys, kv_virt, step_live = sparsity.translate_tables(
        jnp.asarray(bm.kv_index), jnp.asarray(bm.step_live),
        jnp.asarray(page_table, jnp.int32).reshape(-1), n_pages,
    )

    qt = q.reshape(1, s, kvh, g, hd).transpose(0, 2, 3, 1, 4).reshape(kvh, g, s, hd)
    qt = jnp.pad(qt, ((0, 0), (0, 0), (0, sq_pad - s), (0, 0)))

    layer, (k_pool, v_pool, k_scale, v_scale) = _kernel_pools(
        layer, k_pool, v_pool, k_scale, v_scale
    )
    y = fa.mha_prefill(
        qt, k_pool, v_pool, layer, kv_phys, step_live,
        scale=1.0 / math.sqrt(hd), causal=causal, window=window,
        s_q=s, s_kv=s, q_tile=tq, kv_tile=page, interpret=_interpret(),
        kv_virt=kv_virt, k_scale=k_scale, v_scale=v_scale,
    )
    y = y[:, :, :s].reshape(1, kvh, g, s, hd)
    return y.transpose(0, 3, 1, 2, 4).reshape(1, s, h, hd)


def flash_paged_chunk(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    start: jax.Array,
    ntok: jax.Array,
    page_table: jax.Array,
    *,
    page: int,
    layer: jax.Array | int = 0,
    spec: AttentionSpec | None = None,
    kv_live: int | None = None,
    ring_window: int | None = None,
    ring_tiles: int | None = None,
    page_range: tuple[int, int] | None = None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Paged form of :func:`flash_chunk`: q (B, C, H, hd) mixed rows over
    layer ``layer`` of the shared pool, stored stacked by layer (L, KV,
    n_pages * page, hd) and read in place, each row reading through its own
    ``page_table`` row (B, n_vtiles).  ``k_scale`` / ``v_scale`` are a
    quantized pool's (L, KV, n_pages, 1, page) scales (as in
    :func:`flash_paged_prefill`).  The per-row chunk tables are built in
    VIRTUAL tile space (identical liveness to the contiguous engine) and
    translated to physical pages — the kernel grid never visits a dead or
    unallocated tile, and ``kv_live`` buckets the virtual extent exactly as
    the contiguous path buckets its cache truncation.

    ``ring_window`` / ``ring_tiles`` select the mod-window form: the page
    table has ``ring_tiles`` slots reused in phase, the live tables hold
    ABSOLUTE tiles trailing each row's frontier, and the fine mask windows on
    absolute positions — a sliding-window cache in ``ring_tiles`` pages.

    ``page_range=(lo, hi)`` runs the MESH-LOCAL form: the pools are ONE shard
    of a page-sharded cache (pages ``lo..hi-1``), the translated tables mask
    out pages the shard does not own and rebase the rest, so this shard's
    grid prefetches only its own pages.  The result is the shard's partial
    attention over its local pages; cross-shard reassembly needs the online-
    softmax stat merge (a ring/allgather of (m, l, acc)), which is the
    remaining hardware-shakeout item — the serving gate exercises the XLA
    gather path, whose per-shard gathers reassemble by summation."""
    spec = spec or AttentionSpec(impl="flash_kernel")
    pattern, arg, _, window = canonical_pattern(
        spec.pattern, spec.pattern_arg, True, None
    )
    b, c, h, hd = q.shape
    kvh, n_pages = _pool_dims(k_pool, page)
    g = h // kvh
    cp = _round_up(c, 8)

    start = jnp.asarray(start, jnp.int32).reshape(-1)
    if ring_tiles is not None:
        # ring rows mask purely by causal frontier + absolute window; the
        # virtual extent must cover absolute positions, not the ring span
        pattern, arg = "dense", None
        window = ring_window if window is None else min(window, ring_window)
        skv = _round_up(max(int(kv_live or 1), 1), page)
        kv_index, step_live = sparsity.ring_chunk_tables(
            start, ntok, c, window, page, ring_tiles
        )
    else:
        skv = _virtual_extent(page_table, page, kv_live)
        kv_index, step_live = sparsity.chunk_live_tables(
            pattern, start, ntok, c, skv, spec.q_tile, page,
            window=window, pattern_arg=arg,
        )
    if page_range is not None:
        n_pages = _local_pool_bound(page_range, n_pages)
    kv_phys, kv_virt, step_live = sparsity.translate_tables(
        kv_index, step_live, page_table, n_pages, ring_tiles=ring_tiles,
        page_range=page_range,
    )

    qt = q.reshape(b, c, kvh, g, hd).transpose(0, 2, 3, 1, 4)
    qt = jnp.pad(qt, ((0, 0), (0, 0), (0, 0), (0, cp - c), (0, 0)))

    layer, (k_pool, v_pool, k_scale, v_scale) = _kernel_pools(
        layer, k_pool, v_pool, k_scale, v_scale
    )
    y = fa.mha_chunk_paged(
        qt, k_pool, v_pool, layer, start, kv_phys, kv_virt, step_live,
        scale=1.0 / math.sqrt(hd), window=window, s_kv=skv,
        q_tile=spec.q_tile, kv_tile=page, pattern=pattern, pattern_arg=arg,
        interpret=_interpret(), k_scale=k_scale, v_scale=v_scale,
    )
    y = y[:, :, :, :c]
    return y.transpose(0, 3, 1, 2, 4).reshape(b, c, h, hd)


def flash_paged_decode(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    cur_len: jax.Array,
    page_table: jax.Array,
    *,
    page: int,
    layer: jax.Array | int = 0,
    spec: AttentionSpec | None = None,
    kv_live: int | None = None,
    ring_window: int | None = None,
    ring_tiles: int | None = None,
    page_range: tuple[int, int] | None = None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Paged form of :func:`flash_decode`: q (B, H, hd) over layer ``layer``
    of the shared pool, stored stacked by layer (L, KV, n_pages * page, hd)
    and read in place (scales as in :func:`flash_paged_prefill`).

    Each row's per-position live-tile table (the same
    :func:`repro.core.sparsity.decode_live_tables` the contiguous kernel
    prefetches) is translated to physical page ids; the fine mask runs on
    the virtual positions, so a freed or never-allocated tile is simply
    absent and the softmax matches the contiguous engine bit-for-bit.

    ``ring_window`` / ``ring_tiles`` select the mod-window form: positions
    are unbounded (``cur_len`` may exceed any cache extent), the live tables
    hold the absolute tiles trailing the frontier, and the same-modulus page
    table hands back the phase-reused physical pages.

    ``page_range`` selects the mesh-local form (see
    :func:`flash_paged_chunk`): the pools are one page shard, tables mask
    and rebase to the shard's own pages."""
    spec = spec or AttentionSpec(impl="flash_kernel")
    pattern, arg, _, window = canonical_pattern(
        spec.pattern, spec.pattern_arg, True, None
    )
    b, h, hd = q.shape
    kvh, n_pages = _pool_dims(k_pool, page)
    g = h // kvh
    gp = _round_up(g, 8)

    cl_rows = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32).reshape(-1), (b,))
    if ring_tiles is not None:
        window = ring_window if window is None else min(window, ring_window)
        kv_index, step_live = sparsity.ring_decode_tables(
            cl_rows, window, page, ring_tiles
        )
    else:
        skv = _virtual_extent(page_table, page, kv_live)
        kv_index, step_live = sparsity.decode_live_tables(
            pattern, cl_rows, skv, spec.q_tile, page, window=window, pattern_arg=arg
        )
    if page_range is not None:
        n_pages = _local_pool_bound(page_range, n_pages)
    kv_phys, kv_virt, step_live = sparsity.translate_tables(
        kv_index, step_live, page_table, n_pages, ring_tiles=ring_tiles,
        page_range=page_range,
    )

    qt = jnp.pad(q.reshape(b, kvh, g, hd), ((0, 0), (0, 0), (0, gp - g), (0, 0)))

    layer, (k_pool, v_pool, k_scale, v_scale) = _kernel_pools(
        layer, k_pool, v_pool, k_scale, v_scale
    )
    y = fa.mha_decode_paged(
        qt, k_pool, v_pool, layer, cl_rows, kv_phys, kv_virt, step_live,
        scale=1.0 / math.sqrt(hd), window=window, kv_tile=page,
        interpret=_interpret(), k_scale=k_scale, v_scale=v_scale,
    )
    return y[:, :, :g].reshape(b, h, hd)


def fnet_mixing_kernel(x: jax.Array, max_radix: int = sd.MAX_RADIX_COMPLEX) -> jax.Array:
    """Kernel-backed FNet mixing: Re(DFT_seq(DFT_hidden(x))) over the last two
    axes — the AT-all replacement running through the fused pipeline."""
    seq, hid = x.shape[-2], x.shape[-1]
    yr, yi = dft_1d(x, None, sd.plan_stages(hid, max_radix))
    yr2 = jnp.swapaxes(yr, -1, -2)
    yi2 = jnp.swapaxes(yi, -1, -2)
    zr, _ = dft_1d(yr2, yi2, sd.plan_stages(seq, max_radix))
    return jnp.swapaxes(zr, -1, -2)
