"""Fused flash-attention kernels (Pallas, TPU target) — online softmax on
VMEM-resident score tiles, iterating only *live* kv tiles.

This is the paper's §IV orchestration applied to the attention AT-all itself:
the (q_tile x kv_tile) score block is computed, masked, softmax-normalised and
contracted against V entirely in VMEM — the score matrix never touches HBM,
vs one full round trip (write + softmax read + probs write + einsum read) for
the block-oriented XLA form (Fig. 2's memory-bound pathology).  Token tiles
stream through the grid exactly like :mod:`repro.kernels.monarch_bpmm`: one
HBM read of Q/K/V and one HBM write of O per tile, with the TPU DMA engine
double-buffering the next tile against MXU compute ({Load | Cal | Store}).

Block sparsity (§III butterfly-sparsity): both kernels take a packed
per-q-row *live kv-tile index map* (:mod:`repro.core.sparsity`) as
scalar-prefetch arguments.  The kv grid axis has extent ``max_live`` (the
widest row's live count), and the BlockSpec index maps dereference the table —
so statically-dead kv tiles are never part of the grid: no DMA is issued for
them and no MXU step runs.  Rows narrower than ``max_live`` pad with repeats
of tile 0 flagged dead; padded steps skip compute under ``pl.when`` and
revisit an already-streamed block.  A fine in-tile mask (causal diagonal,
window edge, padded keys) keeps partially-live boundary tiles exact.

Prefill kernel
    grid = (batch x kv_heads, gqa_group, q_tiles, max_live_kv_tiles); the
    table is static per (pattern, shape).  Running max / sum-exp / out
    accumulators live in VMEM scratch and carry across kv steps (the online
    softmax).

Decode kernel
    flash-decode: grid = (batch x kv_heads, max_live); the table is *traced*
    per-row data (each request's live tile set over the cache at its own
    position — ragged batches truncate independently).  Cache-length masking
    arrives as a per-row additive bias row.

Layouts (pre-padded by :mod:`repro.kernels.ops`):
    prefill  q: (BK, G, Sq, D)   k, v: (L, BK, Skv, D)   y: (BK, G, Sq, D)
             layer: (1,) int32, the index into k/v's leading axis
             kv_index, step_live: (q_tiles, max_live) int32
    decode   q: (BK, Gp, D)      k, v: (BK, Skv, D)   bias: (BK, Skv)
             kv_index, step_live: (BK, max_live) int32
    with BK = batch * kv_heads, G the GQA group, D the padded head dim (for
    the paged pool, which is never padded, the model's head width).
The paged kernels (and ``mha_prefill``, which serves the paged admission
prefill too) read the pool where it is stored, stacked by layer: the layer
is one more scalar-prefetch operand and the leading coordinate of the K/V
(and scale) index maps, so no call slices a layer out of the stack.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.sparsity import _decode_live_jnp, pick_pattern_tiles

__all__ = [
    "mha_prefill",
    "mha_chunk",
    "mha_decode",
    "mha_chunk_paged",
    "mha_decode_paged",
    "pick_tiles",
    "NEG_INF",
]

NEG_INF = -1e30  # finite stand-in: exp(NEG_INF - m) underflows but never NaNs
_LANES = 128  # running-stat scratch is lane-replicated for TPU tiling


def pick_tiles(s_q: int, s_kv: int, q_tile: int, kv_tile: int) -> tuple[int, int]:
    """Clamp the spec's tile sizes to the (hardware-aligned) problem size.

    Delegates to :func:`repro.core.sparsity.pick_pattern_tiles` — block maps
    and kernels must agree on the effective tile grid."""
    return pick_pattern_tiles(s_q, s_kv, q_tile, kv_tile)


def _layer_operand(layer) -> jax.Array:
    """The (1,) int32 scalar-prefetch operand naming the stacked pool's
    layer a kernel reads."""
    return jnp.asarray(layer, jnp.int32).reshape(1)


def _prefill_kernel(
    ly_ref, kvi_ref, lv_ref, vt_ref, q_ref, k_ref, v_ref, *refs,
    scale: float, causal: bool, window: int | None, s_q: int, s_kv: int,
    q_tile: int, kv_tile: int, quantized: bool = False,
):
    # quantized pools append per-row scale tiles after v: the K scales
    # multiply the score columns and the V scales the probabilities, so the
    # dequant never touches the (tk, d) tiles
    if quantized:
        ksc_ref, vsc_ref, y_ref, m_ref, l_ref, acc_ref = refs
    else:
        ksc_ref = vsc_ref = None
        y_ref, m_ref, l_ref, acc_ref = refs
    i = pl.program_id(2)
    jj = pl.program_id(3)
    nj = pl.num_programs(3)
    # vt is the VIRTUAL kv-tile (token positions); kvi drives the DMA and is
    # either the same tile (contiguous cache) or its physical page (paged)
    j = vt_ref[i, jj]

    @pl.when(jj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # table-padding steps (rows narrower than max_live) carry no live block
    @pl.when(lv_ref[i, jj] > 0)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (tq, d)
        k = k_ref[0].astype(jnp.float32)  # (tk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (tq, tk)
        if ksc_ref is not None:  # per-key dequant scale, along the lanes
            s = s * ksc_ref[0, 0]

        # fine mask: padded keys + causal diagonal + window edge inside the
        # (pattern-live) tile — block-level pruning already happened in the map
        qpos = i * q_tile + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = j * kv_tile + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < s_kv
        if causal:
            mask &= qpos >= kpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]  # (tq, LANES), lane-replicated
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)  # broadcasts back to (tq, LANES)
        alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])
        # explicit re-mask: when a row is still fully masked m_new == NEG_INF
        # and exp(s - m_new) would be 1, not 0
        p = jnp.where(mask, jnp.exp(s - m_new[:, :1]), 0.0)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        if vsc_ref is not None:  # sum_j p_j (vs_j v_j) == sum_j (p_j vs_j) v_j
            p = p * vsc_ref[0, 0]
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(jj == nj - 1)
    def _flush():
        l = l_ref[:, :1]
        y_ref[0, 0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(y_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "scale", "causal", "window", "s_q", "s_kv", "q_tile", "kv_tile", "interpret",
    ),
)
def mha_prefill(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    layer: jax.Array,
    kv_index: jax.Array,
    step_live: jax.Array,
    *,
    scale: float,
    causal: bool,
    window: int | None,
    s_q: int,
    s_kv: int,
    q_tile: int,
    kv_tile: int,
    interpret: bool = False,
    kv_virt: jax.Array | None = None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """q: (BK, G, Sq_pad, D) -> y same shape; k, v: (L, BK, Skv_pad, D), a
    stack of which the kernel reads entry ``layer`` ((1,) int32, scalar
    prefetch) in place.  A single cache is passed as ``k[None]`` with layer
    0, a free reshape.

    ``kv_index`` / ``step_live``: (Sq_pad/q_tile, max_live) packed live
    kv-tile map (:class:`repro.core.sparsity.BlockMap`) — the kv grid axis
    iterates the table, not the full tile range.  ``s_q`` / ``s_kv`` are the
    true (pre-padding) lengths; padded key columns are masked inside the
    kernel, padded query rows are sliced off by the ops wrapper.

    ``kv_virt`` (same shape as ``kv_index``) splits the table in two for a
    *paged* cache: ``kv_index`` then holds PHYSICAL page ids into a shared
    pool (``k``/``v`` are the pool, one page per kv tile) while ``kv_virt``
    holds the virtual tile the fine position mask is computed from
    (:func:`repro.core.sparsity.translate_tables`).  Defaults to
    ``kv_index`` — the contiguous identity mapping.

    ``k_scale`` / ``v_scale`` ((L, BK, Skv_pad / kv_tile, 1, kv_tile)
    float32, or None): per-row dequant scales of a QUANTIZED pool, stored
    stacked like k/v, one (1, kv_tile) lane row per (layer, BK row, tile) so
    every block's last two dims equal the array's, as the TPU tiling
    requires (:func:`repro.models.transformer.paged_pool_specs`).  The K
    scales multiply the score columns and the V scales the probabilities
    (:mod:`repro.core.quant`); when None the call compiles the exact
    unquantized graph."""
    from jax.experimental.pallas import tpu as pltpu

    bk, g, sq_pad, d = q.shape
    skv_pad = k.shape[2]
    if sq_pad % q_tile or skv_pad % kv_tile:
        raise ValueError(f"padded seqs {(sq_pad, skv_pad)} vs tiles {(q_tile, kv_tile)}")
    nq, max_live = kv_index.shape
    if nq != sq_pad // q_tile:
        raise ValueError(f"kv_index rows {nq} vs q tiles {sq_pad // q_tile}")
    if kv_virt is None:
        kv_virt = kv_index
    quantized = k_scale is not None

    grid = (bk, g, nq, max_live)
    # the leading (layer) block dim is squeezed: the kernel sees the same
    # (1, kv_tile, d) tile as from an unstacked cache
    kv_spec = pl.BlockSpec(
        (None, 1, kv_tile, d),
        lambda b, g, i, jj, ly, kvi, lv, vt: (ly[0], b, kvi[i, jj], 0),
    )
    in_specs = [
        pl.BlockSpec((1, 1, q_tile, d), lambda b, g, i, jj, ly, kvi, lv, vt: (b, g, i, 0)),
        kv_spec,
        kv_spec,
    ]
    args = [
        _layer_operand(layer), kv_index.astype(jnp.int32),
        step_live.astype(jnp.int32), kv_virt.astype(jnp.int32), q, k, v,
    ]
    if quantized:
        sspec = pl.BlockSpec(
            (None, 1, 1, 1, kv_tile),
            lambda b, g, i, jj, ly, kvi, lv, vt: (ly[0], b, kvi[i, jj], 0, 0),
        )
        in_specs += [sspec, sspec]
        args += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # layer, kv_index, step_live, kv_virt drive the DMA
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, q_tile, d), lambda b, g, i, jj, ly, kvi, lv, vt: (b, g, i, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((q_tile, _LANES), jnp.float32),
            pltpu.VMEM((q_tile, _LANES), jnp.float32),
            pltpu.VMEM((q_tile, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _prefill_kernel, scale=scale, causal=causal, window=window,
            s_q=s_q, s_kv=s_kv, q_tile=q_tile, kv_tile=kv_tile,
            quantized=quantized,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(*args)


def _chunk_kernel(
    start_ref, kvi_ref, lv_ref, q_ref, k_ref, v_ref, y_ref, m_ref, l_ref, acc_ref,
    *, scale: float, window: int | None, s_kv: int, q_tile: int, kv_tile: int,
    n_kv_tiles: int, pattern: str, pattern_arg: int | None,
):
    b = pl.program_id(0)
    jj = pl.program_id(2)
    nj = pl.num_programs(2)
    j = kvi_ref[b, jj]  # the streamed kv-tile index (per-row traced table)
    start = start_ref[b]  # absolute position of this row's first chunk query

    @pl.when(jj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(lv_ref[b, jj] > 0)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (cp, d)
        k = k_ref[0].astype(jnp.float32)  # (tk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (cp, tk)

        # per-row causal frontier: query at absolute position start+i attends
        # keys <= its own position — the newest readable cache row is the
        # query itself, so the frontier is also the written-cache mask
        qpos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = j * kv_tile + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = (kpos < s_kv) & (qpos >= kpos)
        if window is not None:
            mask &= kpos > qpos - window
        if pattern != "dense":
            # per-QUERY pattern gate: the chunk table is the union over the
            # q-tile rows the chunk spans; each query keeps only its own
            # q-tile's row (the same liveness the decode tables trace)
            mask &= _decode_live_jnp(
                pattern, qpos // q_tile, j, n_kv_tiles, q_tile, kv_tile,
                window, pattern_arg,
            )
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])
        p = jnp.where(mask, jnp.exp(s - m_new[:, :1]), 0.0)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(jj == nj - 1)
    def _flush():
        l = l_ref[:, :1]
        y_ref[0, 0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(y_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "scale", "window", "s_kv", "q_tile", "kv_tile", "pattern",
        "pattern_arg", "interpret",
    ),
)
def mha_chunk(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    start: jax.Array,
    kv_index: jax.Array,
    step_live: jax.Array,
    *,
    scale: float,
    window: int | None,
    s_kv: int,
    q_tile: int,
    kv_tile: int,
    pattern: str = "dense",
    pattern_arg: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Mixed chunked-prefill attention over a shared KV cache.

    q: (BK, Gp, C_pad, D) — each row's chunk of queries at absolute positions
    ``start[b] .. start[b]+C-1``; k, v: (BK, Skv_pad, D) the (truncated)
    cache; ``kv_index`` / ``step_live``: (BK, max_live) per-row packed live
    kv-tile tables (:func:`repro.core.sparsity.chunk_live_tables`) — traced
    data, so rows mid-prompt, rows decoding one token, and idle rows all run
    the same grid while streaming only their own live tiles.  ``q_tile`` is
    the *pattern* q-tile granularity (absolute position space), not the chunk
    length.  Returns (BK, Gp, C_pad, D)."""
    from jax.experimental.pallas import tpu as pltpu

    bk, g, cp, d = q.shape
    skv_pad = k.shape[1]
    if skv_pad % kv_tile:
        raise ValueError(f"padded cache {skv_pad} vs kv tile {kv_tile}")
    if kv_index.shape[0] != bk or start.shape[0] != bk:
        raise ValueError(
            f"table rows {kv_index.shape[0]} / start rows {start.shape[0]} vs BK {bk}"
        )
    max_live = kv_index.shape[1]

    grid = (bk, g, max_live)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # start, kv_index, step_live
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, cp, d), lambda b, gg, jj, st, kvi, lv: (b, gg, 0, 0)),
            pl.BlockSpec((1, kv_tile, d), lambda b, gg, jj, st, kvi, lv: (b, kvi[b, jj], 0)),
            pl.BlockSpec((1, kv_tile, d), lambda b, gg, jj, st, kvi, lv: (b, kvi[b, jj], 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, cp, d), lambda b, gg, jj, st, kvi, lv: (b, gg, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((cp, _LANES), jnp.float32),
            pltpu.VMEM((cp, _LANES), jnp.float32),
            pltpu.VMEM((cp, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _chunk_kernel, scale=scale, window=window, s_kv=s_kv,
            q_tile=q_tile, kv_tile=kv_tile, n_kv_tiles=skv_pad // kv_tile,
            pattern=pattern, pattern_arg=pattern_arg,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(
        start.astype(jnp.int32), kv_index.astype(jnp.int32),
        step_live.astype(jnp.int32), q, k, v,
    )


def _decode_kernel(
    kvi_ref, lv_ref, q_ref, k_ref, v_ref, bias_ref, y_ref, m_ref, l_ref, acc_ref,
    *, scale: float,
):
    b = pl.program_id(0)
    jj = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(jj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(lv_ref[b, jj] > 0)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale  # (gp, d)
        k = k_ref[0].astype(jnp.float32)  # (tk, d)
        v = v_ref[0].astype(jnp.float32)
        bias = bias_ref[0].astype(jnp.float32)  # (tk,): 0 | NEG_INF
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) + bias[None, :]  # (gp, tk)
        valid = bias[None, :] > 0.5 * NEG_INF

        m_prev = m_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])
        p = jnp.where(valid, jnp.exp(s - m_new[:, :1]), 0.0)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(jj == nj - 1)
    def _flush():
        l = l_ref[:, :1]
        y_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(y_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "kv_tile", "interpret")
)
def mha_decode(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    bias: jax.Array,
    kv_index: jax.Array,
    step_live: jax.Array,
    *,
    scale: float,
    kv_tile: int,
    interpret: bool = False,
) -> jax.Array:
    """Flash-decode: q (BK, Gp, D); k, v (BK, Skv_pad, D); bias (BK, Skv_pad)
    per-row additive mask (0 for live keys, NEG_INF for padded / beyond the
    row's cur_len — ragged batches mask each request independently).

    ``kv_index`` / ``step_live``: (BK, max_live) per-row live kv-tile tables
    (:func:`repro.core.sparsity.decode_live_tables`) — the grid's kv extent is
    ``max_live``, not the cache tile count, so a short request against a deep
    cache streams only its own written (and pattern-live) tiles.
    Returns (BK, Gp, D)."""
    from jax.experimental.pallas import tpu as pltpu

    bk, gp, d = q.shape
    skv_pad = k.shape[1]
    if skv_pad % kv_tile:
        raise ValueError(f"padded cache {skv_pad} vs kv tile {kv_tile}")
    if bias.shape != (bk, skv_pad):
        raise ValueError(f"bias {bias.shape} vs expected {(bk, skv_pad)}")
    if kv_index.shape[0] != bk:
        raise ValueError(f"kv_index rows {kv_index.shape[0]} vs BK {bk}")
    max_live = kv_index.shape[1]

    grid = (bk, max_live)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, gp, d), lambda b, jj, kvi, lv: (b, 0, 0)),
            pl.BlockSpec((1, kv_tile, d), lambda b, jj, kvi, lv: (b, kvi[b, jj], 0)),
            pl.BlockSpec((1, kv_tile, d), lambda b, jj, kvi, lv: (b, kvi[b, jj], 0)),
            pl.BlockSpec((1, kv_tile), lambda b, jj, kvi, lv: (b, kvi[b, jj])),
        ],
        out_specs=pl.BlockSpec((1, gp, d), lambda b, jj, kvi, lv: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((gp, _LANES), jnp.float32),
            pltpu.VMEM((gp, _LANES), jnp.float32),
            pltpu.VMEM((gp, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(kv_index.astype(jnp.int32), step_live.astype(jnp.int32), q, k, v, bias)


# --------------------------------------------------------------------------
# Paged grids: the kv tables hold PHYSICAL page ids into a batch-shared pool
# --------------------------------------------------------------------------


def _decode_kernel_paged(
    ly_ref, cl_ref, kvi_ref, vt_ref, lv_ref, q_ref, k_ref, v_ref, *refs,
    scale: float, window: int | None, kv_tile: int, quantized: bool = False,
):
    if quantized:
        ksc_ref, vsc_ref, y_ref, m_ref, l_ref, acc_ref = refs
    else:
        ksc_ref = vsc_ref = None
        y_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    jj = pl.program_id(2)
    nj = pl.num_programs(2)
    jv = vt_ref[b, jj]  # virtual tile: token positions for the fine mask
    cl = cl_ref[b]  # the row's live cache length (pos + 1)

    @pl.when(jj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(lv_ref[b, jj] > 0)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (gp, d)
        k = k_ref[0].astype(jnp.float32)  # (tk, d) — one physical page
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (gp, tk)
        if ksc_ref is not None:  # per-key dequant scale, along the lanes
            s = s * ksc_ref[0, 0]
        # fine mask from VIRTUAL positions: the page holds virtual tile jv,
        # so its t-th row is absolute position jv*kv_tile + t
        kpos = jv * kv_tile + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = kpos < cl
        if window is not None:
            valid &= kpos > cl - 1 - window
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])
        p = jnp.where(valid, jnp.exp(s - m_new[:, :1]), 0.0)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        if vsc_ref is not None:  # sum_j p_j (vs_j v_j) == sum_j (p_j vs_j) v_j
            p = p * vsc_ref[0, 0]
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(jj == nj - 1)
    def _flush():
        l = l_ref[:, :1]
        y_ref[0, 0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(y_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "window", "kv_tile", "interpret")
)
def mha_decode_paged(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    layer: jax.Array,
    cur_len: jax.Array,
    kv_index: jax.Array,
    kv_virt: jax.Array,
    step_live: jax.Array,
    *,
    scale: float,
    window: int | None,
    kv_tile: int,
    interpret: bool = False,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Flash-decode over a PAGED cache: q (B, KV, Gp, D); k, v are the global
    page pool as stored, stacked by layer, (L, KV, n_pages * kv_tile, D) —
    no batch axis, every row reads the pool through its own table, and the
    DMA reads layer ``layer`` ((1,) int32, scalar prefetch) where it lives.
    ``kv_index`` (B, max_live) holds
    physical page ids (the DMA target), ``kv_virt`` the matching virtual kv
    tiles (the fine mask's position base), ``step_live`` the packed liveness
    (:func:`repro.core.sparsity.translate_tables`).  ``cur_len`` (B,) is each
    row's live length in virtual token space; the grid never visits a dead or
    unallocated tile.  ``k_scale`` / ``v_scale`` ((L, KV, n_pages, 1,
    kv_tile) float32, or None) carry a quantized pool's per-row dequant
    scales through the SAME layer and page indirection (layout as in
    :func:`mha_prefill`).  Returns (B, KV, Gp, D)."""
    from jax.experimental.pallas import tpu as pltpu

    b, kvh, gp, d = q.shape
    pool_rows = k.shape[2]
    if pool_rows % kv_tile:
        raise ValueError(f"pool rows {pool_rows} vs kv tile {kv_tile}")
    if kv_index.shape[0] != b or kv_virt.shape != kv_index.shape:
        raise ValueError(
            f"tables {kv_index.shape}/{kv_virt.shape} vs batch {b}"
        )
    max_live = kv_index.shape[1]
    quantized = k_scale is not None

    grid = (b, kvh, max_live)
    kv_spec = pl.BlockSpec(
        (None, 1, kv_tile, d),
        lambda b, h, jj, ly, cl, kvi, vt, lv: (ly[0], h, kvi[b, jj], 0),
    )
    in_specs = [
        pl.BlockSpec((1, 1, gp, d), lambda b, h, jj, ly, cl, kvi, vt, lv: (b, h, 0, 0)),
        kv_spec,
        kv_spec,
    ]
    args = [
        _layer_operand(layer), cur_len.astype(jnp.int32),
        kv_index.astype(jnp.int32), kv_virt.astype(jnp.int32),
        step_live.astype(jnp.int32), q, k, v,
    ]
    if quantized:
        sspec = pl.BlockSpec(
            (None, 1, 1, 1, kv_tile),
            lambda b, h, jj, ly, cl, kvi, vt, lv: (ly[0], h, kvi[b, jj], 0, 0),
        )
        in_specs += [sspec, sspec]
        args += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,  # layer, cur_len, kv_index, kv_virt, step_live
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, gp, d), lambda b, h, jj, ly, cl, kvi, vt, lv: (b, h, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((gp, _LANES), jnp.float32),
            pltpu.VMEM((gp, _LANES), jnp.float32),
            pltpu.VMEM((gp, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _decode_kernel_paged, scale=scale, window=window, kv_tile=kv_tile,
            quantized=quantized,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(*args)


def _chunk_kernel_paged(
    ly_ref, start_ref, kvi_ref, vt_ref, lv_ref, q_ref, k_ref, v_ref, *refs,
    scale: float, window: int | None, s_kv: int,
    q_tile: int, kv_tile: int, n_kv_tiles: int, pattern: str,
    pattern_arg: int | None, quantized: bool = False,
):
    if quantized:
        ksc_ref, vsc_ref, y_ref, m_ref, l_ref, acc_ref = refs
    else:
        ksc_ref = vsc_ref = None
        y_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    jj = pl.program_id(3)
    nj = pl.num_programs(3)
    jv = vt_ref[b, jj]  # virtual tile (positions); DMA used the physical id
    start = start_ref[b]

    @pl.when(jj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(lv_ref[b, jj] > 0)
    def _step():
        q = q_ref[0, 0, 0].astype(jnp.float32) * scale  # (cp, d)
        k = k_ref[0].astype(jnp.float32)  # (tk, d) — one physical page
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (cp, tk)
        if ksc_ref is not None:  # per-key dequant scale, along the lanes
            s = s * ksc_ref[0, 0]

        qpos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = jv * kv_tile + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = (kpos < s_kv) & (qpos >= kpos)
        if window is not None:
            mask &= kpos > qpos - window
        if pattern != "dense":
            mask &= _decode_live_jnp(
                pattern, qpos // q_tile, jv, n_kv_tiles, q_tile, kv_tile,
                window, pattern_arg,
            )
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])
        p = jnp.where(mask, jnp.exp(s - m_new[:, :1]), 0.0)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        if vsc_ref is not None:  # sum_j p_j (vs_j v_j) == sum_j (p_j vs_j) v_j
            p = p * vsc_ref[0, 0]
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(jj == nj - 1)
    def _flush():
        l = l_ref[:, :1]
        y_ref[0, 0, 0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(y_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "scale", "window", "s_kv", "q_tile", "kv_tile", "pattern",
        "pattern_arg", "interpret",
    ),
)
def mha_chunk_paged(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    layer: jax.Array,
    start: jax.Array,
    kv_index: jax.Array,
    kv_virt: jax.Array,
    step_live: jax.Array,
    *,
    scale: float,
    window: int | None,
    s_kv: int,
    q_tile: int,
    kv_tile: int,
    pattern: str = "dense",
    pattern_arg: int | None = None,
    interpret: bool = False,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Mixed chunked-prefill attention over a PAGED shared KV cache.

    q: (B, KV, G, C_pad, D); k, v: the global page pool as stored, stacked
    by layer, (L, KV, n_pages * kv_tile, D), read at layer ``layer`` ((1,)
    int32, scalar prefetch) in place.  ``kv_index`` (B, max_live) physical
    page ids, ``kv_virt``
    the matching virtual kv tiles, ``step_live`` packed liveness — the
    translated form of :func:`repro.core.sparsity.chunk_live_tables`.
    ``s_kv`` is the VIRTUAL cache length (fine masks index virtual token
    positions; the per-query pattern gate runs on virtual tiles).  Same grid
    semantics as :func:`mha_chunk` with the batch and kv-head axes split so
    the pool needs no per-row copy.  ``k_scale`` / ``v_scale`` ((L, KV,
    n_pages, 1, kv_tile) float32, or None): quantized-pool per-row dequant
    scales, layer- and page-indirected like K/V (layout as in
    :func:`mha_prefill`).
    Returns (B, KV, G, C_pad, D)."""
    from jax.experimental.pallas import tpu as pltpu

    b, kvh, g, cp, d = q.shape
    pool_rows = k.shape[2]
    if pool_rows % kv_tile:
        raise ValueError(f"pool rows {pool_rows} vs kv tile {kv_tile}")
    if kv_index.shape[0] != b or start.shape[0] != b:
        raise ValueError(
            f"table rows {kv_index.shape[0]} / start rows {start.shape[0]} vs B {b}"
        )
    max_live = kv_index.shape[1]
    quantized = k_scale is not None

    grid = (b, kvh, g, max_live)
    kv_spec = pl.BlockSpec(
        (None, 1, kv_tile, d),
        lambda b, h, gg, jj, ly, st, kvi, vt, lv: (ly[0], h, kvi[b, jj], 0),
    )
    in_specs = [
        pl.BlockSpec(
            (1, 1, 1, cp, d),
            lambda b, h, gg, jj, ly, st, kvi, vt, lv: (b, h, gg, 0, 0),
        ),
        kv_spec,
        kv_spec,
    ]
    args = [
        _layer_operand(layer), start.astype(jnp.int32),
        kv_index.astype(jnp.int32), kv_virt.astype(jnp.int32),
        step_live.astype(jnp.int32), q, k, v,
    ]
    if quantized:
        sspec = pl.BlockSpec(
            (None, 1, 1, 1, kv_tile),
            lambda b, h, gg, jj, ly, st, kvi, vt, lv: (ly[0], h, kvi[b, jj], 0, 0),
        )
        in_specs += [sspec, sspec]
        args += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,  # layer, start, kv_index, kv_virt, step_live
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, 1, cp, d),
            lambda b, h, gg, jj, ly, st, kvi, vt, lv: (b, h, gg, 0, 0),
        ),
        scratch_shapes=[
            pltpu.VMEM((cp, _LANES), jnp.float32),
            pltpu.VMEM((cp, _LANES), jnp.float32),
            pltpu.VMEM((cp, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _chunk_kernel_paged, scale=scale, window=window, s_kv=s_kv,
            q_tile=q_tile, kv_tile=kv_tile,
            n_kv_tiles=-(-s_kv // kv_tile), pattern=pattern,
            pattern_arg=pattern_arg, quantized=quantized,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(*args)
