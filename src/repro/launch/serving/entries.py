"""Compiled serve entry points: jitted prefill/decode/mixed/chunk/paged
factories with sharded KV caches and page pools.

`make_serve_fns` builds the two classic compiled entry points the dry-run
exercises (`prefill_32k` lowers prefill; `decode_32k` / `long_500k` lower
decode_step); with ``ragged=True`` the prefill takes per-request prompt
lengths and the decode takes a (B,) position vector instead of a batch-wide
scalar.  `make_mixed_fn` builds the third, unified entry point: one jitted
``mixed_step`` where every batch row consumes a per-row token count — a
prompt chunk, one decode token, or nothing.  `make_paged_fns` builds the
page-pool family; its pools shard over the mesh's ``pages`` axis when one
exists (see :func:`repro.models.transformer.paged_pool_specs`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import quant
from repro.core.attention import override_attention
from repro.distributed import sharding as shd
from repro.models import model as M
from repro.models import transformer as tf
from repro.models.config import ModelConfig

__all__ = [
    "cache_shardings",
    "abstract_cache",
    "make_serve_fns",
    "make_mixed_fn",
    "make_slot_chunk_fn",
    "make_paged_fns",
    "zero_pools",
]


def cache_shardings(cfg: ModelConfig, mesh: Mesh, batch: int, cache_len: int):
    return shd.sharding_tree(tf.cache_specs(cfg, batch, cache_len), mesh, M.rules_for(cfg))


def abstract_cache(cfg: ModelConfig, batch: int, cache_len: int):
    specs = tf.cache_specs(cfg, batch, cache_len)
    dt = jnp.dtype(cfg.dtype)
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dt),
        specs,
        is_leaf=lambda x: isinstance(x, shd.ParamSpec),
    )


def zero_pools(cfg: ModelConfig, mesh: Mesh, n_pages: int, page: int,
               cross_pages: int | None = None, kv_dtype: str = "bf16"):
    """Zero-initialised paged KV pools placed at their MESH shardings — on a
    mesh with a ``pages`` axis the page rows land sharded from the start, so
    the donated entry-point calls never reshard a committed replicated
    array.

    ``kv_dtype`` != 'bf16' stores the self-attention K/V leaves at the
    quantized width and adds their float32 ``*_scale`` leaves
    (:func:`repro.models.transformer.paged_pool_specs`); cross pools and
    everything else stay at the config's cache dtype."""
    specs = tf.paged_pool_specs(
        cfg, n_pages, page, cross_pages=cross_pages, kv_dtype=kv_dtype
    )
    shards = shd.sharding_tree(specs, mesh, M.rules_for(cfg))
    base = jnp.dtype(cfg.dtype)
    store = quant.kv_store_dtype(kv_dtype, base)

    def leaf_dtype(path):
        names = [p.key for p in path if hasattr(p, "key")]
        if names and names[-1].endswith("_scale"):
            return jnp.dtype(jnp.float32)
        if "attn" in names and names[-1] in ("k", "v"):
            return store
        return base

    return jax.tree_util.tree_map_with_path(
        lambda path, s, sh: jax.device_put(
            jnp.zeros(s.shape, leaf_dtype(path)), sh
        ),
        specs, shards,
        is_leaf=lambda x: isinstance(x, shd.ParamSpec),
    )


def _entry_shardings(cfg: ModelConfig, mesh: Mesh, batch: int, cache_len: int):
    """Shared setup of every serve entry-point factory: resolved runtime +
    the param / cache / token / replicated shardings.  One definition so the
    prefill, decode, mixed-wave and slot-chunk compiles can never diverge."""
    rt = M.resolve_runtime(cfg, mesh)
    p_shard = shd.sharding_tree(M.build_specs(cfg), mesh, M.rules_for(cfg))
    c_shard = cache_shardings(cfg, mesh, batch, cache_len)
    tok_shard = NamedSharding(
        mesh, P(tuple(a for a in ("pod", "data") if a in mesh.axis_names))
    )
    rep = NamedSharding(mesh, P())
    return rt, p_shard, c_shard, tok_shard, rep


def make_serve_fns(
    cfg: ModelConfig,
    mesh: Mesh,
    *,
    batch: int,
    cache_len: int,
    attn_impl: str | None = None,
    attn_pattern: str | None = None,
    ragged: bool = False,
):
    """Returns (prefill_fn, decode_fn).

    ``ragged=False`` (static batch): prefill_fn(params, batch_dict) and
    decode_fn(params, caches, tokens, pos-scalar).  ``ragged=True``:
    prefill_fn(params, batch_dict, lengths (B,)) gathers each row's last real
    token and decode_fn takes pos as a (B,) per-request position vector.

    ``attn_impl`` / ``attn_pattern`` override the config's attention
    execution form / block-sparsity pattern for this serving instance (e.g.
    "flash_kernel" + "butterfly" on a single-chip deployment).

    ``decode_fn`` takes an optional trailing ``kv_live`` (static int): a
    host-known bound on every row's live cache length.  Attention then
    streams only the first ``kv_live`` cache rows — each distinct value
    compiles once, so callers should bucket it (the engine uses powers of
    two)."""
    cfg = override_attention(cfg, impl=attn_impl, pattern=attn_pattern)
    rt, p_shard, c_shard, tok_shard, rep = _entry_shardings(
        cfg, mesh, batch, cache_len
    )

    if ragged:
        prefill = jax.jit(
            lambda params, b, lengths: tf.prefill(
                params, cfg, b, rt, cache_len=cache_len, lengths=lengths
            ),
            in_shardings=(p_shard, None, rep),
            out_shardings=(tok_shard, c_shard),
        )
        pos_shard = rep  # (B,) per-request positions, replicated
    else:
        prefill = jax.jit(
            lambda params, b: tf.prefill(params, cfg, b, rt, cache_len=cache_len),
            in_shardings=(p_shard, None),
            out_shardings=(tok_shard, c_shard),
        )
        pos_shard = rep
    jitted: dict[int | None, object] = {}

    def decode(params, caches, tokens, pos, kv_live: int | None = None):
        fn = jitted.get(kv_live)
        if fn is None:
            fn = jax.jit(
                lambda params, caches, tokens, pos: tf.decode_step(
                    params, cfg, caches, tokens, pos, rt, kv_live=kv_live
                ),
                in_shardings=(p_shard, c_shard, tok_shard, pos_shard),
                out_shardings=(tok_shard, c_shard),
                donate_argnums=(1,),
            )
            jitted[kv_live] = fn
        return fn(params, caches, tokens, pos)

    return prefill, decode


def make_mixed_fn(
    cfg: ModelConfig,
    mesh: Mesh,
    *,
    batch: int,
    cache_len: int,
    chunk: int,
    attn_impl: str | None = None,
    attn_pattern: str | None = None,
):
    """The unified mixed-step entry point: one compiled function advances the
    whole batch, each row consuming ``ntok[b]`` tokens (0 idle / 1 decode /
    2..chunk prompt chunk) at positions ``pos[b]..``.

    Returned callable: ``mixed(params, caches, tokens (B,C) host prompt
    chunks, nxt (B,) device feedback tokens, use_nxt (B,) bool, pos (B,),
    ntok (B,), kv_live)``.  Decode rows take their input token from ``nxt``
    (the previous step's on-device argmax — the host never syncs on token
    values), prefill rows from ``tokens``.  ``kv_live`` buckets compile
    per value, like the decode entry point."""
    cfg = override_attention(cfg, impl=attn_impl, pattern=attn_pattern)
    rt, p_shard, c_shard, tok_shard, rep = _entry_shardings(
        cfg, mesh, batch, cache_len
    )
    jitted: dict[int | None, object] = {}

    def mixed(params, caches, tokens, nxt, use_nxt, pos, ntok,
              kv_live: int | None = None):
        if tokens.shape != (batch, chunk):
            raise ValueError(
                f"tokens {tokens.shape} vs compiled chunk shape {(batch, chunk)}"
            )
        fn = jitted.get(kv_live)
        if fn is None:
            def _step(params, caches, tokens, nxt, use_nxt, pos, ntok):
                col0 = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :] == 0
                toks = jnp.where(use_nxt[:, None] & col0, nxt[:, None], tokens)
                return tf.mixed_step(
                    params, cfg, caches, toks, pos, ntok, rt, kv_live=kv_live
                )

            fn = jax.jit(
                _step,
                in_shardings=(p_shard, c_shard, tok_shard, tok_shard, rep, rep, rep),
                out_shardings=(tok_shard, c_shard),
                donate_argnums=(1,),
            )
            jitted[kv_live] = fn
        return fn(params, caches, tokens, nxt, use_nxt, pos, ntok)

    return mixed


def make_slot_chunk_fn(
    cfg: ModelConfig,
    mesh: Mesh,
    *,
    batch: int,
    cache_len: int,
    chunk: int,
    attn_impl: str | None = None,
    attn_pattern: str | None = None,
):
    """``mixed_step`` at its other ragged shape, (1, chunk): stream one
    prompt chunk into ONE slot of the shared cache at a traced slot index.

    Returned callable: ``chunk_fn(params, caches, tokens (1, C), slot, pos,
    ntok, kv_live)`` -> (logits (vocab,) at the chunk's last valid token,
    full updated caches).  The slot's cache rows are sliced to a batch-1
    view, the chunk runs through the exact same mixed_step / chunk-kernel
    path, and the updated rows are written back in place (donated) — so a
    chunk call costs ``C x kv_live`` attention for one row, not
    ``B x C x kv_live`` for the whole batch.  Compiles once per ``kv_live``
    bucket, like the decode entry point."""
    cfg = override_attention(cfg, impl=attn_impl, pattern=attn_pattern)
    rt, p_shard, c_shard, _, rep = _entry_shardings(cfg, mesh, batch, cache_len)
    jitted: dict[int | None, object] = {}

    def chunk_fn(params, caches, tokens, slot, pos, ntok,
                 kv_live: int | None = None):
        if tokens.shape != (1, chunk):
            raise ValueError(
                f"tokens {tokens.shape} vs compiled chunk shape {(1, chunk)}"
            )
        fn = jitted.get(kv_live)
        if fn is None:
            def _step(params, caches, tokens, slot, pos, ntok):
                sub = jax.tree.map(
                    lambda c: jax.lax.dynamic_slice_in_dim(c, slot, 1, axis=1),
                    caches,
                )
                logits, new_sub = tf.mixed_step(
                    params, cfg, sub, tokens, jnp.reshape(pos, (1,)),
                    jnp.reshape(ntok, (1,)), rt, kv_live=kv_live,
                )
                caches = jax.tree.map(
                    lambda c, w: jax.lax.dynamic_update_slice_in_dim(
                        c, w.astype(c.dtype), slot, axis=1
                    ),
                    caches,
                    new_sub,
                )
                return logits[0], caches

            fn = jax.jit(
                _step,
                in_shardings=(p_shard, c_shard, rep, rep, rep, rep),
                out_shardings=(rep, c_shard),
                donate_argnums=(1,),
            )
            jitted[kv_live] = fn
        return fn(params, caches, tokens, slot, pos, ntok)

    return chunk_fn


def make_paged_fns(
    cfg: ModelConfig,
    mesh: Mesh,
    *,
    n_pages: int,
    page: int,
    chunk: int,
    attn_impl: str | None = None,
    attn_pattern: str | None = None,
    cross_pages: int | None = None,
    kv_dtype: str = "bf16",
):
    """Compiled entry points of the PAGED serve engine: ``(prefill, decode,
    chunk_fn, copy_fn, encode_fn)`` over one global page pool instead of
    per-slot ``cache_len`` reservations.

    * ``prefill(params, caches, b, lengths, pt_row)`` — batch-1 admission
      prefill scattered through the request's page-table row (retraces per
      prompt bucket, like the ragged contiguous prefill).
    * ``decode(params, caches, tokens (B,1), pos (B,), pt (B,nv), kv_live)``
      — the ragged decode wave; every row reads the pool through its own
      page-table row, bucketed per ``kv_live``.
    * ``chunk_fn(params, caches, tokens (1,C), pt_row (1,nv), pos, ntok,
      kv_live)`` — one prompt chunk streamed straight into the pool.  No
      slot slice/insert dance: the pool is already shared, the page table IS
      the slot.
    * ``copy_fn(caches, src, dst)`` — copy-on-write page duplication
      (:func:`repro.models.transformer.paged_copy_page`); src/dst are traced
      page ids, so the whole prefix-sharing machinery compiles exactly one
      extra program.

    With ``cross_pages`` (encoder-decoder stacks) the pools grow per-slot
    read-only cross pools; ``decode`` / ``chunk_fn`` then take a trailing
    cross-table argument and a fifth entry point appears:

    * ``encode_fn(params, caches, frames (1, S, D), ct_row (1, n_ct))`` —
      run the encoder ONCE and scatter every decoder slot's cross KV into
      the cross pool through ``ct_row``
      (:func:`repro.models.transformer.paged_encode`); the written pages
      are read-only for the rest of their life and alias freely.

    ``decode.jit_for(kv_live)`` / ``chunk_fn.jit_for(kv_live)`` return the
    jitted program of one bucket (built on first use), so a caller can
    ``.lower(...)`` it — to compile ahead or to inspect the compiled module.

    All entry points donate the pools, and the calls write them in place:
    the layer scan carries the stacked pools and scatters each layer's new
    rows into them (:func:`repro.models.transformer.run_stack`), so the
    pools a call returns are its input buffers.  The page tables are tiny
    replicated int32 arrays refreshed from host state every call.  On a
    mesh with a ``pages`` axis the pool's page rows are SHARDED over it —
    each device holds the contiguous physical range the host allocator's
    matching shard places into — while the page tables stay replicated
    (they are the ownership record both sides read).

    ``kv_dtype`` selects the pool storage width (bf16 | int8 | fp8_e4m3) —
    the entry points themselves are layout-agnostic (the caches tree flows
    through opaquely), only the pool SHARDING tree must know about the
    quantized pools' extra ``*_scale`` leaves."""
    cfg = override_attention(cfg, impl=attn_impl, pattern=attn_pattern)
    rt = M.resolve_runtime(cfg, mesh)
    p_shard = shd.sharding_tree(M.build_specs(cfg), mesh, M.rules_for(cfg))
    pool_shard = shd.sharding_tree(
        tf.paged_pool_specs(
            cfg, n_pages, page, cross_pages=cross_pages, kv_dtype=kv_dtype
        ),
        mesh, M.rules_for(cfg),
    )
    tok_shard = NamedSharding(
        mesh, P(tuple(a for a in ("pod", "data") if a in mesh.axis_names))
    )
    rep = NamedSharding(mesh, P())

    # each program is a named function, so its jitted module is named after
    # it (``jit_paged_decode``) in compile logs and profiler traces
    def paged_prefill(params, caches, b, lengths, pt):
        return tf.paged_prefill(
            params, cfg, b, rt, caches=caches, page_table=pt, page=page,
            lengths=lengths,
        )

    prefill = jax.jit(
        paged_prefill,
        in_shardings=(p_shard, pool_shard, None, rep, rep),
        out_shardings=(tok_shard, pool_shard),
        donate_argnums=(1,),
    )

    dec_jit: dict[int | None, object] = {}

    def decode_jit(kv_live: int | None = None):
        fn = dec_jit.get(kv_live)
        if fn is None:
            def paged_decode(params, caches, tokens, pos, pt, ct=None):
                return tf.decode_step(
                    params, cfg, caches, tokens, pos, rt, kv_live=kv_live,
                    page_table=pt, page=page, cross_table=ct,
                )

            cross = (rep,) if cross_pages is not None else ()
            fn = jax.jit(
                paged_decode,
                in_shardings=(p_shard, pool_shard, tok_shard, rep, rep)
                + cross,
                out_shardings=(tok_shard, pool_shard),
                donate_argnums=(1,),
            )
            dec_jit[kv_live] = fn
        return fn

    def decode(params, caches, tokens, pos, pt, kv_live: int | None = None,
               ct=None):
        fn = decode_jit(kv_live)
        if cross_pages is not None:
            return fn(params, caches, tokens, pos, pt, ct)
        return fn(params, caches, tokens, pos, pt)

    chk_jit: dict[int | None, object] = {}

    def chunk_jit(kv_live: int | None = None):
        fn = chk_jit.get(kv_live)
        if fn is None:
            def paged_chunk(params, caches, tokens, pt, pos, ntok, ct=None):
                logits, caches = tf.mixed_step(
                    params, cfg, caches, tokens, jnp.reshape(pos, (1,)),
                    jnp.reshape(ntok, (1,)), rt, kv_live=kv_live,
                    page_table=pt, page=page, cross_table=ct,
                )
                return logits[0], caches

            cross = (rep,) if cross_pages is not None else ()
            fn = jax.jit(
                paged_chunk,
                in_shardings=(p_shard, pool_shard, rep, rep, rep, rep) + cross,
                out_shardings=(rep, pool_shard),
                donate_argnums=(1,),
            )
            chk_jit[kv_live] = fn
        return fn

    def chunk_fn(params, caches, tokens, pt, pos, ntok,
                 kv_live: int | None = None, ct=None):
        if tokens.shape != (1, chunk):
            raise ValueError(
                f"tokens {tokens.shape} vs compiled chunk shape {(1, chunk)}"
            )
        fn = chunk_jit(kv_live)
        if cross_pages is not None:
            return fn(params, caches, tokens, pt, pos, ntok, ct)
        return fn(params, caches, tokens, pt, pos, ntok)

    # the jitted program of each kv_live bucket, for lowering and inspection
    decode.jit_for = decode_jit
    chunk_fn.jit_for = chunk_jit

    def paged_copy_page(caches, src, dst):
        return tf.paged_copy_page(caches, src, dst, page)

    copy_fn = jax.jit(
        paged_copy_page,
        in_shardings=(pool_shard, rep, rep),
        out_shardings=pool_shard,
        donate_argnums=(0,),
    )

    encode_fn = None
    if cross_pages is not None:
        def paged_encode(params, caches, frames, ct):
            return tf.paged_encode(
                params, cfg, frames, rt, caches=caches, cross_table=ct,
                page=page,
            )

        encode_fn = jax.jit(
            paged_encode,
            in_shardings=(p_shard, pool_shard, None, rep),
            out_shardings=pool_shard,
            donate_argnums=(1,),
        )

    return prefill, decode, chunk_fn, copy_fn, encode_fn
