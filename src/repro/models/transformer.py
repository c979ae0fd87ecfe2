"""Periodic-pattern transformer runtime: dense / MoE / SSM / hybrid / enc-dec.

One scan-over-periods executes every architecture: a period is a static tuple
of layer slots (attn|mamba|fft mixer x dense|moe|none FFN), parameters are
stacked over periods, and caches mirror the slot structure.  The paper's
technique enters exclusively through the linear-layer specs (BPMM sites) and
the `fft` mixer slot (AT-all replacement), so dense baselines and butterfly
variants share every line of runtime code.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import api
from repro.core import quant
from repro.core.fft_mixing import fnet_mixing
from repro.distributed.sharding import ParamSpec, constrain
from repro.models import params as pp
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.config import ModelConfig, Slot
from repro.core import sparsity
from repro.models.layers import (
    Runtime,
    apply_rope,
    gather_pages,
    gelu,
    layer_norm,
    rms_norm,
    run_attention,
    run_chunk_attention,
    run_decode_attention,
    run_paged_chunk_attention,
    run_paged_decode_attention,
    run_paged_prefill_attention,
    silu,
)

Params = dict[str, Any]

# --------------------------------------------------------------------------
# Param specs
# --------------------------------------------------------------------------


def _norm_specs(cfg: ModelConfig, n_periods: int) -> dict:
    out = {"w": ParamSpec((n_periods, cfg.d_model), (None, None), init="zeros")}
    if cfg.norm == "layernorm":
        out["b"] = ParamSpec((n_periods, cfg.d_model), (None, None), init="zeros")
    return out


def _stack(tree: dict, n: int) -> dict:
    return {
        k: ParamSpec((n, *s.shape), (None,) + s.axes, s.init, s.scale)
        for k, s in tree.items()
    }


def attn_specs(cfg: ModelConfig, n_periods: int) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    bias = cfg.qkv_bias
    sq = api.LinearSpec(d, h * hd, cfg.butterfly.for_site("qkv"), use_bias=bias)
    sk = api.LinearSpec(d, kv * hd, cfg.butterfly.for_site("qkv"), use_bias=bias)
    so = api.LinearSpec(h * hd, d, cfg.butterfly.for_site("out"))
    out = {
        "wq": _stack(pp.linear_specs(sq), n_periods),
        "wk": _stack(pp.linear_specs(sk), n_periods),
        "wv": _stack(pp.linear_specs(sk), n_periods),
        "wo": _stack(pp.linear_specs(so, axes=("tp", "fsdp")), n_periods),
    }
    if cfg.qk_norm:
        out["q_norm"] = ParamSpec((n_periods, hd), (None, None), init="zeros")
        out["k_norm"] = ParamSpec((n_periods, hd), (None, None), init="zeros")
    return out


def ffn_specs(cfg: ModelConfig, n_periods: int) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    s1 = api.LinearSpec(d, f, cfg.butterfly.for_site("ffn"))
    s2 = api.LinearSpec(f, d, cfg.butterfly.for_site("ffn"))
    out = {
        "w1": _stack(pp.linear_specs(s1), n_periods),
        "w2": _stack(pp.linear_specs(s2, axes=("tp", "fsdp")), n_periods),
    }
    if cfg.act == "swiglu":
        out["w3"] = _stack(pp.linear_specs(s1), n_periods)
    return out


def slot_specs(cfg: ModelConfig, slot: Slot, n_periods: int, cross: bool = False) -> dict:
    out: dict = {"mixer_norm": _norm_specs(cfg, n_periods)}
    if slot.mixer == "attn":
        out["attn"] = attn_specs(cfg, n_periods)
    elif slot.mixer == "mamba":
        out["mamba"] = ssm_mod.mamba_specs(cfg, n_periods)
    if cross:
        out["cross_norm"] = _norm_specs(cfg, n_periods)
        out["cross"] = attn_specs(cfg, n_periods)
    if slot.ffn != "none":
        out["ffn_norm"] = _norm_specs(cfg, n_periods)
        if slot.ffn == "moe":
            rt_mode = "ep"  # spec sharding falls back automatically if E % tp != 0
            out["moe"] = moe_mod.moe_specs(cfg, n_periods, rt_mode)
        else:
            out["ffn"] = ffn_specs(cfg, n_periods)
    return out


def model_specs(cfg: ModelConfig) -> dict:
    specs: dict = {
        "embed": ParamSpec((cfg.vocab, cfg.d_model), ("fsdp", "tp"), scale=1.0),
        "head": ParamSpec((cfg.d_model, cfg.vocab), ("fsdp", "tp")),
        "final_norm": _norm_specs(cfg, 1),
        "layers": {
            f"slot{j:02d}": slot_specs(cfg, s, cfg.n_periods)
            for j, s in enumerate(cfg.period_slots)
        },
    }
    if cfg.family == "encdec":
        enc_slot = Slot("fft" if cfg.butterfly.fft_attention else "attn", "dense")
        specs["encoder"] = {
            "layers": {
                "slot00": slot_specs(cfg, enc_slot, cfg.n_enc_layers)
            },
            "final_norm": _norm_specs(cfg, 1),
        }
        # decoder slots get cross-attention
        specs["layers"] = {
            f"slot{j:02d}": slot_specs(cfg, s, cfg.n_periods, cross=True)
            for j, s in enumerate(cfg.period_slots)
        }
    return specs


# --------------------------------------------------------------------------
# Apply
# --------------------------------------------------------------------------


def _norm(nparams: dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.norm == "layernorm":
        return layer_norm(x, nparams["w"], nparams["b"], cfg.norm_eps)
    return rms_norm(x, nparams["w"], cfg.norm_eps)


def _proj(aparams, cfg, x, name, heads):
    site = {"wq": "qkv", "wk": "qkv", "wv": "qkv", "wo": "out"}[name]
    bias = cfg.qkv_bias and name != "wo"
    if name == "wo":
        spec = api.LinearSpec(cfg.n_heads * cfg.head_dim, cfg.d_model, cfg.butterfly.for_site(site))
    else:
        spec = api.LinearSpec(cfg.d_model, heads * cfg.head_dim, cfg.butterfly.for_site(site), use_bias=bias)
    return pp.apply_linear_p(aparams[name], spec, x)


def _ring_place(c: jax.Array, lengths: jax.Array, klen: int) -> jax.Array:
    """Reorder a full-length KV tensor into ring order: slot ``t`` holds the
    newest key whose absolute position ≡ t (mod klen) below the row's length.

    c: (B, S, KV, hd) -> (B, klen, KV, hd).  A later decode write at
    ``pos % klen`` then lands exactly on the oldest in-window key — for any
    prompt length, not just multiples of the window.  Rows with
    ``lengths[b] < klen`` leave slots >= lengths[b] as clamped duplicates;
    the decode-side ``cur_len`` mask never reads them.
    """
    t = jnp.arange(klen)
    last = lengths.astype(jnp.int32)[:, None] - 1  # (B, 1)
    p = last - ((last - t[None, :]) % klen)
    p = jnp.clip(p, 0, c.shape[1] - 1)
    return jnp.take_along_axis(c, p[:, :, None, None], axis=1)


def _paged_kv_write(
    pool: jax.Array,
    new: jax.Array,
    rows: jax.Array,
    valid: jax.Array,
    page_table: jax.Array,
    page: int,
    ring_tiles: int | None = None,
    scale: jax.Array | None = None,
    layer: jax.Array | int | None = None,
):
    """Page-table-indirected masked scatter: token KV at absolute positions
    ``rows`` (B, C) lands at ``page_table[b, rows // page] * page + rows %
    page`` of the flat pool (n_pages * page, KV, hd).  Rows that are invalid
    (beyond ``ntok`` / ``lengths``) or whose virtual tile is unallocated
    (sentinel id) scatter out of bounds and are dropped — a row can never
    clobber a page it does not own.

    ``ring_tiles`` is the mod-window modulus: the table has ``ring_tiles``
    slots and absolute tile ``rows // page`` writes slot
    ``(rows // page) % ring_tiles`` — the paged replacement for the
    contiguous ``_ring_place`` write path, phase-aligned for any position.

    Copy-on-write contract: with prefix sharing, a page table entry may
    alias a physical page other requests (or the host radix cache) also
    read.  The scatter itself cannot know refcounts, so the HOST must
    guarantee every tile overlapping a write range is exclusively held
    before the step — ``ServeLoop._ensure_writable`` forks shared pages
    (``PagePool.fork`` + :func:`paged_copy_page`) and repoints the table
    entry, making the first divergent write land in a private copy.

    ``scale`` selects the QUANTIZED pool form: the pool stores int8 /
    fp8_e4m3 pages and ``scale`` is the matching (n_pages * page, KV) f32
    per-row-per-head scale pool.  Each written row is quantized
    independently (:func:`repro.core.quant.quantize_rows` — symmetric absmax
    over head_dim, the scheme resolved from ``pool.dtype``) and its scale
    scatters through the SAME flat page-row index, so a page and its scales
    can never diverge — CoW copies, radix aliasing, rings, and shard
    transfers carry them as one unit.  Returns ``(pool, scale)`` in that
    form, the pool alone otherwise.

    ``layer`` selects the STACKED form: ``pool`` (and ``scale``) keep their
    leading ``n_periods`` axis and the rows land at ``(layer, flat)`` — an
    in-place scatter of B x C rows into the pool :func:`run_stack` carries
    through its layer scan, with no copy of the layer's pool."""
    lead = () if layer is None else (layer,)
    n_rows = pool.shape[len(lead)]
    vt = rows // page
    if ring_tiles is not None:
        vt = vt % ring_tiles
    vt = jnp.clip(vt, 0, page_table.shape[1] - 1)
    phys = jnp.take_along_axis(page_table, vt, axis=1)
    flat = phys * page + rows % page
    flat = jnp.where(valid & (phys < n_rows // page), flat, n_rows).reshape(-1)
    if scale is None:
        return pool.at[(*lead, flat)].set(
            new.astype(pool.dtype).reshape(-1, *new.shape[2:]), mode="drop"
        )
    qv, sc = quant.quantize_rows(new, pool.dtype)  # (B, C, KV, hd), (B, C, KV)
    pool = pool.at[(*lead, flat)].set(
        qv.reshape(-1, *qv.shape[2:]), mode="drop"
    )
    scale = scale.at[(*lead, flat)].set(
        sc.reshape(-1, *sc.shape[2:]).astype(scale.dtype), mode="drop"
    )
    return pool, scale


def _layer_of(pool: jax.Array | None, layer: jax.Array | None):
    """Layer ``layer`` of a stacked pool leaf (the leaf itself when
    ``layer`` is None: the pool is already one layer's)."""
    if pool is None or layer is None:
        return pool
    return jax.lax.dynamic_index_in_dim(pool, layer, axis=0, keepdims=False)


def paged_copy_page(caches: dict, src: jax.Array, dst: jax.Array, page: int) -> dict:
    """Copy physical page ``src``'s rows onto page ``dst`` in every pool leaf
    — the device half of a copy-on-write fork.  ``src``/``dst`` are traced
    scalars so the host engine compiles this once; positions the copied page
    holds that the forking request has not written yet are either identical
    prefix KV (shared tokens) or masked by the causal frontier until the
    request overwrites them."""

    def cp(c):  # (n_periods, n_pages * page, KV, hd)
        rows = jax.lax.dynamic_slice_in_dim(c, src * page, page, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(c, rows, dst * page, axis=1)

    return jax.tree.map(cp, caches)


def apply_attention(
    aparams: dict,
    cfg: ModelConfig,
    x: jax.Array,
    rt: Runtime,
    *,
    causal: bool,
    positions: jax.Array,
    mode: str,  # train | encode | prefill | decode | mixed
    cache: dict | None = None,
    pos: jax.Array | None = None,
    kv_source: jax.Array | None = None,
    is_cross: bool = False,
    use_rope: bool = True,
    lengths: jax.Array | None = None,  # (B,) true prompt lengths (ragged prefill)
    attn_pattern: str | None = None,  # per-slot sparsity override (hybrid stacks)
    kv_live: int | None = None,  # static live-cache bound (sparse serve decode)
    ntok: jax.Array | None = None,  # (B,) valid chunk tokens (mixed step)
    page_table: jax.Array | None = None,  # (B, n_vtiles) paged-cache indirection
    page: int | None = None,  # tokens per page (static; = the kv tile)
    layer: jax.Array | None = None,  # this layer's index into stacked pools
):
    b, s, d = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    spec = cfg.attention_spec
    if attn_pattern is not None:
        spec = dataclasses.replace(spec, pattern=attn_pattern)
    if is_cross or (cfg.sliding_window and spec.sparse):
        # patterns index absolute token positions: cross-attention KV has no
        # such positions, and ring caches store keys in mod-window order —
        # both fall back to the dense map (window sparsity still applies)
        spec = dataclasses.replace(spec, pattern="dense")

    q = _proj(aparams, cfg, x, "wq", h).reshape(b, s, h, hd)
    if is_cross and (mode == "decode" or kv_source is None):
        k_new = v_new = None  # cross-attention KV lives in the cache / pages
    else:
        src = kv_source if is_cross else x
        k_new = _proj(aparams, cfg, src, "wk", kv).reshape(b, src.shape[1], kv, hd)
        v_new = _proj(aparams, cfg, src, "wv", kv).reshape(b, src.shape[1], kv, hd)

    if cfg.qk_norm:
        q = rms_norm(q, aparams["q_norm"], cfg.norm_eps)
        if k_new is not None:
            k_new = rms_norm(k_new, aparams["k_norm"], cfg.norm_eps)
    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        if k_new is not None and not is_cross:
            k_new = apply_rope(k_new, positions, cfg.rope_theta)

    new_cache = None
    if page_table is not None and is_cross:
        # READ-ONLY shared page range: the encoder's cross KV was prefilled
        # once into refcounted pages (:func:`paged_encode`) and this request's
        # ``page_table`` merely aliases them — decode/chunk steps never write
        # a cross page, so copy-on-write can never trigger and every decoder
        # sharing the encoder output shares the physical pages outright.
        assert cache is not None and page is not None
        kg = gather_pages(_layer_of(cache["k"], layer), page_table, cfg.enc_seq, page)
        vg = gather_pages(_layer_of(cache["v"], layer), page_table, cfg.enc_seq, page)
        if mode == "decode":
            out = run_decode_attention(
                q[:, 0], kg, vg, None, spec=spec, rt=rt
            )[:, None]
        else:  # mixed chunk rows: every query reads the whole encoder output
            out = run_attention(q, kg, vg, spec=spec, causal=False, rt=rt)
        new_cache = cache  # pools untouched by construction
    elif page_table is not None:
        # paged KV cache: ``cache`` is the GLOBAL page pool (n_pages * page,
        # KV, hd) shared by every batch row; ``page_table`` (B, n_vtiles)
        # maps each row's virtual kv tiles to physical pages.  Writes are
        # page-table-indirected masked scatters (invalid / unallocated rows
        # drop), reads go through the translated live-tile tables — the same
        # liveness maps as the contiguous engine, one extra indirection.
        # A sliding-window config turns the table into a MOD-WINDOW RING:
        # absolute tile j lives in slot j % ring_tiles, positions are
        # unbounded, and the fine masks window on absolute positions — the
        # paged replacement for the contiguous ``_ring_place`` path.
        # With ``layer`` the pools are the whole stack :func:`run_stack`
        # carries: the write lands at (layer, row) in place, and attention
        # reads layer ``layer`` of the written pools.
        assert cache is not None and pos is not None and page is not None
        ring_tiles = ring_window = None
        if cfg.sliding_window:
            _, _, _, sw = sparsity.canonical_pattern(
                spec.pattern, spec.pattern_arg, True, None
            )
            ring_window = min(cfg.sliding_window, sw) if sw else cfg.sliding_window
            ring_tiles = page_table.shape[1]
            spec = dataclasses.replace(spec, pattern="dense")
        kc, vc = cache["k"], cache["v"]
        # quantized pools carry per-(row, kv_head) scale leaves in the same
        # flat page layout as K/V — absent for bf16 (see repro.core.quant)
        ksc, vsc = cache.get("k_scale"), cache.get("v_scale")

        def write(rows, valid, ring=None):
            """Scatter the new rows into the pools; returns the written pools
            and this layer's (K, V, K scale, V scale) for attention."""
            w = functools.partial(
                _paged_kv_write, rows=rows, valid=valid,
                page_table=page_table, page=page, ring_tiles=ring, layer=layer,
            )
            if ksc is None:
                pools = {"k": w(kc, k_new), "v": w(vc, v_new)}
            else:
                (pk, pks), (pv, pvs) = w(kc, k_new, scale=ksc), w(vc, v_new, scale=vsc)
                pools = {"k": pk, "v": pv, "k_scale": pks, "v_scale": pvs}
            names = ("k", "v", "k_scale", "v_scale")
            return pools, [_layer_of(pools.get(n), layer) for n in names]

        if mode == "mixed":
            assert ntok is not None
            rows = pos[:, None] + jnp.arange(s, dtype=jnp.int32)  # (B, C)
            valid = jnp.arange(s)[None, :] < ntok[:, None]
            new_cache, (kl, vl, ksl, vsl) = write(rows, valid, ring_tiles)
            out = run_paged_chunk_attention(
                q, kl, vl, pos, ntok, page_table, page=page, spec=spec,
                rt=rt, kv_live=kv_live, ring_window=ring_window,
                ring_tiles=ring_tiles, k_scale=ksl, v_scale=vsl,
            )
        elif mode == "decode":
            # every row writes at its own position; a retired slot's page
            # table is all-sentinel so its (garbage) write drops, and a
            # mid-prompt row's write is overwritten by its next chunk before
            # any consequential read — same discipline as the contiguous
            # wave, with the page table enforcing ownership
            rows = pos[:, None]  # (B, 1)
            valid = jnp.ones_like(rows, bool)
            new_cache, (kl, vl, ksl, vsl) = write(rows, valid, ring_tiles)
            out = run_paged_decode_attention(
                q[:, 0], kl, vl, pos + 1, page_table, page=page, spec=spec,
                rt=rt, kv_live=kv_live, ring_window=ring_window,
                ring_tiles=ring_tiles, k_scale=ksl, v_scale=vsl,
            )[:, None]
        elif mode == "prefill":
            if ring_tiles is not None:
                raise ValueError(
                    "mod-window paged caches stream prefill through the "
                    "chunk path; monolithic prefill would wrap the ring"
                )
            rows = jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32)[None, :], (b, s)
            )
            ln = (
                lengths if lengths is not None else jnp.full((b,), s, jnp.int32)
            )
            valid = jnp.arange(s)[None, :] < ln[:, None]
            new_cache, (kl, vl, ksl, vsl) = write(rows, valid)
            out = run_paged_prefill_attention(
                q, k_new, v_new, kl, vl, page_table, page=page, spec=spec,
                rt=rt, k_scale=ksl, v_scale=vsl,
            )
        else:
            raise ValueError(f"paged caches have no {mode!r} mode")
    elif mode == "mixed":
        # mixed chunked-prefill step: row b consumes ntok[b] tokens at
        # absolute positions pos[b] .. pos[b]+ntok[b]-1 (0 = idle slot,
        # 1 = decode, >1 = prompt chunk) — the chunk KV is scattered straight
        # into the shared cache BEFORE attention (in-chunk causal self-
        # attention reads its own keys), and the per-row causal frontier
        # inside run_chunk_attention doubles as the written-cache mask.
        assert cache is not None and pos is not None and ntok is not None
        assert not is_cross, "mixed steps are self-attention only"
        assert not cfg.sliding_window, (
            "mixed chunked prefill needs absolute cache positions; ring "
            "caches go through the admission-prefill path"
        )
        cache_len = cache["k"].shape[1]
        rows = pos[:, None] + jnp.arange(s, dtype=jnp.int32)  # (B, C)
        valid = jnp.arange(s)[None, :] < ntok[:, None]
        # invalid rows scatter out of bounds and are dropped — idle / budget-
        # starved / decode rows never clobber cache rows they don't own
        rows = jnp.where(valid, rows, cache_len)
        upd = jax.vmap(lambda c, n, r: c.at[r].set(n, mode="drop"))
        kc = upd(cache["k"], k_new.astype(cache["k"].dtype), rows)
        vc = upd(cache["v"], v_new.astype(cache["v"].dtype), rows)
        new_cache = {"k": kc, "v": vc}
        out = run_chunk_attention(
            q, kc, vc, pos, ntok, spec=spec, rt=rt, kv_live=kv_live
        )
    elif mode == "decode":
        assert cache is not None and pos is not None
        if not is_cross:  # self-attention: append the token's kv at pos
            cache_len = cache["k"].shape[1]
            wpos = pos % cache_len if cfg.sliding_window else pos
            kn = k_new.astype(cache["k"].dtype)
            vn = v_new.astype(cache["v"].dtype)
            if jnp.ndim(pos) == 0:  # batch-wide position (static batch)
                kc = jax.lax.dynamic_update_slice_in_dim(cache["k"], kn, wpos, axis=1)
                vc = jax.lax.dynamic_update_slice_in_dim(cache["v"], vn, wpos, axis=1)
            else:  # ragged: every request writes at its own position
                upd = jax.vmap(
                    lambda c, n, p: jax.lax.dynamic_update_slice_in_dim(c, n, p, axis=0)
                )
                kc, vc = upd(cache["k"], kn, wpos), upd(cache["v"], vn, wpos)
            new_cache = {"k": kc, "v": vc}
            # live-KV mask (scalar or (B,)): rows beyond min(pos+1, klen) are
            # unwritten — for a sliding-window ring cache their zero-init keys
            # would otherwise score e^0 in the softmax
            cur = jnp.minimum(pos + 1, cache_len)
            out = run_decode_attention(
                q[:, 0], kc, vc, cur, spec=spec, rt=rt,
                kv_live=None if cfg.sliding_window else kv_live,
            )
        else:  # cross-attention: static KV from the encoder pass
            new_cache = cache
            out = run_decode_attention(
                q[:, 0], cache["k"], cache["v"], None, spec=spec, rt=rt
            )
        out = out[:, None]
    else:
        win = cfg.sliding_window if causal else None
        out = run_attention(
            q, k_new, v_new, spec=spec,
            causal=causal and not is_cross, window=win, rt=rt,
        )
        if mode == "prefill":
            kc, vc = k_new, v_new
            win = cfg.sliding_window
            if not is_cross and win and kc.shape[1] > win:
                # keep only the ring window — otherwise the layer scan stacks
                # the full-seq KV for every layer before the final slice
                # (found via the 2-pod mixtral prefill: 120 GiB of temps).
                # Ring (mod-window) order, per-row length: the decode write at
                # pos % klen stays phase-aligned for any prompt length
                ln = (
                    lengths
                    if lengths is not None
                    else jnp.full((b,), kc.shape[1], jnp.int32)
                )
                kc, vc = _ring_place(kc, ln, win), _ring_place(vc, ln, win)
            new_cache = {"k": kc, "v": vc}

    out = _proj(aparams, cfg, out.reshape(b, s, h * hd), "wo", h)
    return out, new_cache


def apply_ffn(fparams: dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    s1 = api.LinearSpec(cfg.d_model, cfg.d_ff, cfg.butterfly.for_site("ffn"))
    s2 = api.LinearSpec(cfg.d_ff, cfg.d_model, cfg.butterfly.for_site("ffn"))
    h = pp.apply_linear_p(fparams["w1"], s1, x)
    if cfg.act == "swiglu":
        h = silu(h) * pp.apply_linear_p(fparams["w3"], s1, x)
    else:
        h = gelu(h)
    return pp.apply_linear_p(fparams["w2"], s2, h)


def apply_slot(
    slot: Slot,
    sparams: dict,
    cfg: ModelConfig,
    x: jax.Array,
    rt: Runtime,
    *,
    mode: str,
    positions: jax.Array,
    cache: dict | None = None,
    pos: jax.Array | None = None,
    enc_out: jax.Array | None = None,
    causal: bool = True,
    lengths: jax.Array | None = None,
    kv_live: int | None = None,
    ntok: jax.Array | None = None,
    page_table: jax.Array | None = None,
    page: int | None = None,
    cross_table: jax.Array | None = None,
    layer: jax.Array | None = None,
):
    """One layer: pre-norm mixer + (optional cross-attn) + pre-norm FFN.
    ``layer`` is set when ``cache`` holds the whole stack of paged pools
    (:func:`run_stack`'s carried form) and names this layer's index in it."""
    aux = jnp.zeros((), jnp.float32)
    new_cache: dict = {}
    hmix = _norm(sparams["mixer_norm"], cfg, x)
    if slot.mixer == "attn":
        mix, c = apply_attention(
            sparams["attn"], cfg, hmix, rt, causal=causal, positions=positions,
            mode=mode, cache=None if cache is None else cache.get("attn"), pos=pos,
            lengths=lengths, attn_pattern=slot.attn_pattern, kv_live=kv_live,
            ntok=ntok, page_table=page_table, page=page, layer=layer,
        )
        if c is not None:
            new_cache["attn"] = c
    elif slot.mixer == "mamba":
        if mode == "decode":
            mix, c = ssm_mod.mamba_decode(sparams["mamba"], cfg, hmix, cache["mamba"], rt)
            new_cache["mamba"] = c
        elif mode == "prefill":
            mix, c = ssm_mod.apply_mamba(sparams["mamba"], cfg, hmix, rt, return_cache=True)
            new_cache["mamba"] = c
        else:
            mix = ssm_mod.apply_mamba(sparams["mamba"], cfg, hmix, rt)
    elif slot.mixer == "fft":
        mix = fnet_mixing(hmix)  # AT-all replacement: parameter-free token mixing
    else:
        raise ValueError(slot.mixer)
    x = x + mix

    if "cross" in sparams and (
        enc_out is not None or mode == "decode" or cross_table is not None
    ):
        hx = _norm(sparams["cross_norm"], cfg, x)
        cmix, cc = apply_attention(
            sparams["cross"], cfg, hx, rt, causal=False, positions=positions,
            mode=mode, cache=None if cache is None else cache.get("cross"), pos=pos,
            kv_source=enc_out, is_cross=True, use_rope=False,
            page_table=cross_table, page=page, layer=layer,
        )
        if cc is not None:
            new_cache["cross"] = cc
        x = x + cmix

    if slot.ffn != "none":
        hffn = _norm(sparams["ffn_norm"], cfg, x)
        if slot.ffn == "moe":
            y, aux = moe_mod.apply_moe(
                sparams["moe"], cfg, hffn, rt, dropless=(mode != "train")
            )
        else:
            y = apply_ffn(sparams["ffn"], cfg, hffn)
        x = x + y
    return x, new_cache, aux


def _boundary(x, rt, cfg=None):
    s = x.shape[1]
    tp = 1
    if rt.mesh is not None and "model" in rt.mesh.axis_names:
        tp = dict(zip(rt.mesh.axis_names, rt.mesh.devices.shape))["model"]
    sp = cfg is None or cfg.boundary_mode == "sp"
    axes = ("batch", "seq" if sp and s % max(tp, 1) == 0 and s > 1 else None, None)
    return constrain(x, axes, rt.mesh, rt.rules)


def run_stack(
    layer_params: dict,
    cfg: ModelConfig,
    x: jax.Array,
    rt: Runtime,
    *,
    slots: tuple[Slot, ...],
    mode: str,
    positions: jax.Array,
    caches: dict | None = None,  # stacked (n_periods, ...) per slot
    pos: jax.Array | None = None,
    enc_out: jax.Array | None = None,
    causal: bool = True,
    lengths: jax.Array | None = None,  # (B,) ragged prompt lengths (prefill)
    kv_live: int | None = None,  # static live-cache bound (sparse serve decode)
    ntok: jax.Array | None = None,  # (B,) valid chunk tokens (mixed step)
    page_table: jax.Array | None = None,  # (B, n_vtiles) paged-cache tables
    page: int | None = None,  # tokens per page (static)
    cross_table: jax.Array | None = None,  # (B, n_ctiles) shared cross pages
):
    """Scan the periodic layer pattern.  Returns (x, new_caches, aux_sum).

    Paged pools (``caches`` with a ``page_table``) ride the scan's CARRY:
    the scan runs over ``(layer_params, layer index)``, layer ``i`` scatters
    its new rows into the stacked pools at ``(i, row)`` and its attention
    reads ``pool[i]``, so the pools that leave the loop are the (donated)
    input buffers written in place — no per-layer restack into a second
    stacked buffer and no whole-pool copy after the loop.  Read-only cross
    pools ride the carry unwritten.  Every other cache (contiguous per-slot
    KV, mamba state) has a batch axis and goes through the scan's
    ``xs``/``ys``; ``cfg.unroll_layers`` slices every cache per layer."""

    def body(carry, per, layer=None):
        x, aux = carry
        p_params, p_cache = per
        new_cache = {}
        for j, slot in enumerate(slots):
            key = f"slot{j:02d}"
            x = _boundary(x, rt, cfg)
            x, c, a = apply_slot(
                slot, p_params[key], cfg, x, rt, mode=mode, positions=positions,
                cache=None if p_cache is None else p_cache[key], pos=pos,
                enc_out=enc_out, causal=causal, lengths=lengths, kv_live=kv_live,
                ntok=ntok, page_table=page_table, page=page,
                cross_table=cross_table, layer=layer,
            )
            new_cache[key] = c
            aux = aux + a
        return (x, aux), new_cache

    if cfg.remat and mode == "train":
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)

    if cfg.unroll_layers:  # cost-probe mode: see ModelConfig.unroll_layers
        n = jax.tree.leaves(layer_params)[0].shape[0]
        carry = (x, jnp.zeros((), jnp.float32))
        outs = []
        for i in range(n):
            p_i = jax.tree.map(lambda a: a[i], layer_params)
            c_i = None if caches is None else jax.tree.map(lambda a: a[i], caches)
            carry, nc = body(carry, (p_i, c_i))
            outs.append(nc)
        (x, aux) = carry
        new_caches = jax.tree.map(lambda *xs: jnp.stack(xs), *outs) if outs else {}
        return x, new_caches, aux

    if caches is not None and page_table is not None:
        def carried(carry, per):
            x, aux, pools = carry
            p_params, i = per
            (x, aux), pools = body((x, aux), (p_params, pools), layer=i)
            return (x, aux, pools), None

        n = jax.tree.leaves(layer_params)[0].shape[0]
        (x, aux, new_caches), _ = jax.lax.scan(
            carried, (x, jnp.zeros((), jnp.float32), caches),
            (layer_params, jnp.arange(n, dtype=jnp.int32)),
        )
    elif caches is None:
        (x, aux), new_caches = jax.lax.scan(
            lambda c, p: body(c, (p, None)), (x, jnp.zeros((), jnp.float32)), layer_params
        )
    else:
        (x, aux), new_caches = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), (layer_params, caches)
        )
    return x, new_caches, aux


# --------------------------------------------------------------------------
# Top level: embed -> stack -> head
# --------------------------------------------------------------------------


def embed_tokens(params: Params, cfg: ModelConfig, tokens: jax.Array, rt: Runtime):
    # cast-then-gather: the distributed gather (and its psum) moves bf16, not
    # the f32 master copy
    table = params["embed"].astype(cfg.dtype)
    return jnp.take(table, tokens, axis=0)


def run_encoder(params: Params, cfg: ModelConfig, frames: jax.Array, rt: Runtime):
    """Stub-frontend encoder (whisper): frames are precomputed embeddings."""
    x = frames.astype(cfg.dtype)
    enc_slot = Slot("fft" if cfg.butterfly.fft_attention else "attn", "dense")
    positions = jnp.arange(x.shape[1])
    x, _, _ = run_stack(
        params["encoder"]["layers"], cfg, x, rt, slots=(enc_slot,),
        mode="encode", positions=positions, causal=False,
    )
    nf = jax.tree.map(lambda a: a[0], params["encoder"]["final_norm"])
    return _norm(nf, cfg, x)


def forward(
    params: Params,
    cfg: ModelConfig,
    batch: dict,
    rt: Runtime,
    *,
    mode: str = "train",
):
    """Returns (logits, aux).  batch: tokens (B,S) [+ img_embeds | frames]."""
    tokens = batch["tokens"]
    x = embed_tokens(params, cfg, tokens, rt)
    if cfg.n_img_tokens and "img_embeds" in batch:
        x = jnp.concatenate([batch["img_embeds"].astype(x.dtype), x], axis=1)
    enc_out = None
    if cfg.family == "encdec":
        enc_out = run_encoder(params, cfg, batch["frames"], rt)
    positions = jnp.arange(x.shape[1])
    x = _boundary(x, rt, cfg)
    x, _, aux = run_stack(
        params["layers"], cfg, x, rt, slots=cfg.period_slots, mode=mode,
        positions=positions, enc_out=enc_out, causal=cfg.causal,
    )
    nf = jax.tree.map(lambda a: a[0], params["final_norm"])
    x = _norm(nf, cfg, x)
    if cfg.n_img_tokens and "img_embeds" in batch:
        x = x[:, batch["img_embeds"].shape[1] :]
    logits = x @ params["head"].astype(x.dtype)
    return logits, aux


def loss_fn(params: Params, cfg: ModelConfig, batch: dict, rt: Runtime):
    """Cross entropy without materialising f32 full-vocab tensors.

    Logits stay in the activation dtype; the exp-sum accumulates in f32
    *inside* the reduction (fused convert), and the label logit is gathered
    per-token before upcasting — the backward pass then scatters a bf16 (not
    f32) cotangent.  This halves+ the dominant memory-roofline term of every
    train cell (found via the qwen3 dry-run probe: three 2.3 GiB f32 copies).
    """
    logits, aux = forward(params, cfg, batch, rt, mode="train")
    labels = batch["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    lmax = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    shifted = logits - lmax  # activation dtype
    sumexp = jnp.sum(jnp.exp(shifted), axis=-1, dtype=jnp.float32)
    lse = jnp.log(sumexp) + lmax[..., 0].astype(jnp.float32)
    ll = jnp.take_along_axis(shifted, jnp.maximum(labels, 0)[..., None], axis=-1)
    ll = ll[..., 0].astype(jnp.float32) + lmax[..., 0].astype(jnp.float32)
    nll = (lse - ll) * mask
    ntok = jnp.maximum(mask.sum(), 1.0)
    loss = nll.sum() / ntok
    zloss = 1e-4 * ((lse * mask) ** 2).sum() / ntok
    total = loss + zloss + cfg.router_aux_coef * aux
    return total, {"loss": loss, "zloss": zloss, "aux": aux, "ntok": ntok}


# --------------------------------------------------------------------------
# Serving: prefill + decode
# --------------------------------------------------------------------------


def prefill(
    params: Params,
    cfg: ModelConfig,
    batch: dict,
    rt: Runtime,
    cache_len: int,
    *,
    lengths: jax.Array | None = None,
):
    """Run the prompt, return (last-token logits, caches padded to cache_len).

    ``lengths`` (B,) enables the ragged form: tokens are *right*-padded (real
    tokens at 0..L-1, so RoPE positions and the causal mask are exact — pad
    tokens sit strictly in the future of every real token and are never
    attended), the returned logits are gathered at each row's own last real
    token, and sliding-window caches are ring-placed per row.  Pad-token KV
    written beyond a row's length is left in the cache; the decode-side
    per-row ``cur_len`` mask (min(pos+1, klen)) never reads it and the first
    decode steps overwrite it in place.  Stateful (mamba) mixers integrate the
    whole padded sequence, so ragged lengths require attention-only stacks.
    """
    tokens = batch["tokens"]
    x = embed_tokens(params, cfg, tokens, rt)
    if cfg.n_img_tokens and "img_embeds" in batch:
        assert lengths is None, "ragged prefill does not support image prefixes"
        x = jnp.concatenate([batch["img_embeds"].astype(x.dtype), x], axis=1)
    enc_out = None
    if cfg.family == "encdec":
        enc_out = run_encoder(params, cfg, batch["frames"], rt)
    positions = jnp.arange(x.shape[1])
    x = _boundary(x, rt, cfg)
    x, caches, _ = run_stack(
        params["layers"], cfg, x, rt, slots=cfg.period_slots, mode="prefill",
        positions=positions, enc_out=enc_out, causal=cfg.causal, lengths=lengths,
    )
    nf = jax.tree.map(lambda a: a[0], params["final_norm"])
    x = _norm(nf, cfg, x)
    if lengths is None:
        last = x[:, -1]
    else:  # per-request last real token
        idx = jnp.clip(lengths.astype(jnp.int32) - 1, 0, x.shape[1] - 1)
        last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
    logits = last @ params["head"].astype(x.dtype)
    caches = _pad_kv_caches(caches, cfg, cache_len)
    return logits, caches


def _pad_kv_caches(caches, cfg: ModelConfig, cache_len: int):
    def fix(slot_cache):
        out = {}
        for name, c in slot_cache.items():
            if name in ("attn",) and c:
                k, v = c["k"], c["v"]
                tgt = min(cache_len, cfg.sliding_window) if cfg.sliding_window else cache_len
                if k.shape[2] < tgt:
                    padw = [(0, 0), (0, 0), (0, tgt - k.shape[2]), (0, 0), (0, 0)]
                    k, v = jnp.pad(k, padw), jnp.pad(v, padw)
                elif k.shape[2] > tgt:
                    k, v = k[:, :, -tgt:], v[:, :, -tgt:]
                out[name] = {"k": k, "v": v}
            else:
                out[name] = c
        return out

    return {key: fix(slot) for key, slot in caches.items()}


def cache_specs(cfg: ModelConfig, batch: int, cache_len: int) -> dict:
    """ParamSpec tree for the decode caches (dry-run stand-ins + shardings).

    Mirrors exactly the structure `run_stack(mode="prefill")` emits, stacked
    over periods.  Attention KV caches shard (batch -> data, seq -> model);
    mamba states shard heads over model when divisible.
    """
    n = cfg.n_periods
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    klen = min(cache_len, cfg.sliding_window) if cfg.sliding_window else cache_len
    out: dict = {}
    for j, slot in enumerate(cfg.period_slots):
        sc: dict = {}
        if slot.mixer == "attn":
            kvspec = ParamSpec(
                (n, batch, klen, kv, hd), (None, "batch", "seq", "tp", None)
            )
            sc["attn"] = {"k": kvspec, "v": kvspec}
        elif slot.mixer == "mamba":
            d_xbc = cfg.d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
            sc["mamba"] = {
                "conv": ParamSpec(
                    (n, batch, cfg.ssm_conv - 1, d_xbc), (None, "batch", None, "tp")
                ),
                "state": ParamSpec(
                    (n, batch, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim),
                    (None, "batch", "tp", None, None),
                ),
            }
        if cfg.family == "encdec":
            ckv = ParamSpec(
                (n, batch, cfg.enc_seq, kv, hd), (None, "batch", "seq", "tp", None)
            )
            sc["cross"] = {"k": ckv, "v": ckv}
        out[f"slot{j:02d}"] = sc
    return out


def paged_pool_specs(
    cfg: ModelConfig,
    n_pages: int,
    page: int,
    cross_pages: int | None = None,
    kv_dtype: str = "bf16",
) -> dict:
    """ParamSpec tree for the paged KV cache: one GLOBAL page pool per
    attention slot, (n_periods, n_pages * page, KV, hd) — no batch axis, no
    per-slot ``cache_len`` reservation.  Resident HBM is the pool; per-request
    footprint is the pages its page table holds, so capacity prices at live
    tiles instead of ``batch x cache_len``.  The pools stay stacked over
    periods through every call: :func:`run_stack` carries them through its
    layer scan and writes layer ``i``'s rows in place at ``(i, row)``.
    Sliding-window configs need no
    special layout here — the ring modulus lives in the page TABLE
    (mod-window slots), the pool is just pages.  Encoder-decoder stacks add a
    per-slot ``cross`` pool of ``cross_pages`` pages holding the encoder
    output's KV as read-only shared page ranges.  Pools shard KV heads over
    the model axis AND pages over the ``pages`` mesh axis: GSPMD partitions
    the row axis contiguously, so shard ``s`` of ``k`` owns physical pages
    ``[s * n_pages/k, (s+1) * n_pages/k)`` — the same ranges the host-side
    :class:`repro.launch.serve.PagePool` shards its free lists over, which
    is what lets :func:`repro.core.sparsity.translate_tables` rebase a
    shard's tables into its local page range.  A mesh without a ``pages``
    axis (every single-chip test mesh) replicates the pools, the old
    behaviour.  The cross pool stays replicated — it is read-only and
    shared, its capacity is not the scaling axis.

    ``kv_dtype`` != 'bf16' adds float32 ``k_scale`` / ``v_scale`` leaves
    shaped (n_periods, n_pages * page, KV) to each self-attention pool —
    one symmetric scale per (row, kv_head), sharded and paged exactly like
    the rows they reconstruct (:mod:`repro.core.quant`).  Cross pools stay
    unquantized: they are written once at encode and read-only shared, so
    capacity pressure (the quantization motive) never lands on them."""
    n = cfg.n_periods
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    out: dict = {}
    for j, slot in enumerate(cfg.period_slots):
        sc: dict = {}
        if slot.mixer == "attn":
            kvspec = ParamSpec(
                (n, n_pages * page, kv, hd), (None, "pages", "tp", None)
            )
            sc["attn"] = {"k": kvspec, "v": kvspec}
            if kv_dtype != "bf16":
                quant.validate_kv_dtype(kv_dtype)
                sspec = ParamSpec((n, n_pages * page, kv), (None, "pages", "tp"))
                sc["attn"]["k_scale"] = sspec
                sc["attn"]["v_scale"] = sspec
        elif slot.mixer == "mamba":
            raise ValueError("paged serving requires attention-only stacks")
        if cfg.family == "encdec":
            cspec = ParamSpec(
                (n, (cross_pages or n_pages) * page, kv, hd),
                (None, None, "tp", None),
            )
            sc["cross"] = {"k": cspec, "v": cspec}
        out[f"slot{j:02d}"] = sc
    return out


def paged_prefill(
    params: Params,
    cfg: ModelConfig,
    batch: dict,
    rt: Runtime,
    *,
    caches: dict,
    page_table: jax.Array,
    page: int,
    lengths: jax.Array | None = None,
):
    """Admission prefill into a PAGED cache: the prompt's KV is scattered
    through the page table into the global pool and attention reads it back
    through the translated block map (batch-1; the page table is one row).
    Returns (last-real-token logits, updated pools) — no contiguous wave, no
    cache insert: the pool already holds the request's pages."""
    if cfg.sliding_window:
        raise ValueError(
            "mod-window paged caches stream prefill through the chunk path"
        )
    if cfg.family == "encdec":
        raise ValueError(
            "encdec paged admission streams decoder chunks after paged_encode"
        )
    tokens = batch["tokens"]
    x = embed_tokens(params, cfg, tokens, rt)
    positions = jnp.arange(x.shape[1])
    x = _boundary(x, rt, cfg)
    x, caches, _ = run_stack(
        params["layers"], cfg, x, rt, slots=cfg.period_slots, mode="prefill",
        positions=positions, caches=caches, causal=cfg.causal, lengths=lengths,
        page_table=page_table, page=page,
        pos=jnp.zeros((tokens.shape[0],), jnp.int32),
    )
    nf = jax.tree.map(lambda a: a[0], params["final_norm"])
    x = _norm(nf, cfg, x)
    if lengths is None:
        last = x[:, -1]
    else:
        idx = jnp.clip(lengths.astype(jnp.int32) - 1, 0, x.shape[1] - 1)
        last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
    logits = last @ params["head"].astype(x.dtype)
    return logits, caches


def paged_encode(
    params: Params,
    cfg: ModelConfig,
    frames: jax.Array,
    rt: Runtime,
    *,
    caches: dict,
    cross_table: jax.Array,
    page: int,
):
    """Run the encoder ONCE and scatter every decoder slot's cross-attention
    KV into the shared cross page pool through ``cross_table`` (one row of
    physical page ids covering ``cfg.enc_seq`` positions).

    The written pages are READ-ONLY for the rest of their life: every decoder
    request sharing this encoder input aliases them via ``PagePool.retain``,
    decode never writes a cross page, so copy-on-write can never trigger and
    cross-attention prefix sharing falls out of the refcounts for free.
    Returns the updated pools (non-cross leaves untouched)."""
    enc_out = run_encoder(params, cfg, frames, rt)
    b, s_enc = enc_out.shape[0], enc_out.shape[1]
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    rows = jnp.broadcast_to(
        jnp.arange(s_enc, dtype=jnp.int32)[None, :], (b, s_enc)
    )
    valid = jnp.ones((b, s_enc), bool)
    ct = jnp.asarray(cross_table, jnp.int32).reshape(b, -1)
    new_caches = dict(caches)
    for j, _slot in enumerate(cfg.period_slots):
        key = f"slot{j:02d}"
        slot_params = params["layers"][key]
        if "cross" not in slot_params or "cross" not in caches[key]:
            continue
        kp, vp = caches[key]["cross"]["k"], caches[key]["cross"]["v"]
        for i in range(cfg.n_periods):
            ap = jax.tree.map(lambda a: a[i], slot_params["cross"])
            k_new = _proj(ap, cfg, enc_out, "wk", kv).reshape(b, s_enc, kv, hd)
            v_new = _proj(ap, cfg, enc_out, "wv", kv).reshape(b, s_enc, kv, hd)
            if cfg.qk_norm:
                k_new = rms_norm(k_new, ap["k_norm"], cfg.norm_eps)
            kp = _paged_kv_write(kp, k_new, rows, valid, ct, page, layer=i)
            vp = _paged_kv_write(vp, v_new, rows, valid, ct, page, layer=i)
        new_caches[key] = {**caches[key], "cross": {"k": kp, "v": vp}}
    return new_caches


def decode_step(
    params: Params,
    cfg: ModelConfig,
    caches: dict,
    tokens: jax.Array,
    pos: jax.Array,
    rt: Runtime,
    *,
    kv_live: int | None = None,
    page_table: jax.Array | None = None,
    page: int | None = None,
    cross_table: jax.Array | None = None,
):
    """One token for the whole batch.  tokens: (B, 1); pos: scalar int32
    (static batch) or (B,) int32 per-request positions (ragged batch —
    RoPE angles, cache write slots, and live-KV masks all go per row).

    ``kv_live`` (static) bounds every row's live cache length — attention
    streams only the first ``kv_live`` cache rows instead of the whole padded
    cache (the serve engine passes its bucketed ``max(pos)+1``)."""
    x = embed_tokens(params, cfg, tokens, rt)
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        positions = jnp.full((x.shape[0], 1), pos, jnp.int32)
    else:
        positions = pos[:, None]
    x, new_caches, _ = run_stack(
        params["layers"], cfg, x, rt, slots=cfg.period_slots, mode="decode",
        positions=positions, caches=caches, pos=pos, causal=cfg.causal,
        kv_live=kv_live, page_table=page_table, page=page,
        cross_table=cross_table,
    )
    nf = jax.tree.map(lambda a: a[0], params["final_norm"])
    x = _norm(nf, cfg, x)
    logits = x[:, 0] @ params["head"].astype(x.dtype)
    return logits, new_caches


def mixed_step(
    params: Params,
    cfg: ModelConfig,
    caches: dict,
    tokens: jax.Array,
    pos: jax.Array,
    ntok: jax.Array,
    rt: Runtime,
    *,
    kv_live: int | None = None,
    page_table: jax.Array | None = None,
    page: int | None = None,
    cross_table: jax.Array | None = None,
):
    """One mixed chunked-prefill/decode step for the whole batch.

    tokens: (B, C); pos: (B,) absolute position of each row's first token;
    ntok: (B,) valid tokens per row — 0 (idle slot), 1 (decode), 2..C (prompt
    chunk).  Row b's tokens land at cache positions ``pos[b]..pos[b]+ntok-1``
    and every query attends its own causal prefix, so prompt chunks stream
    into the shared cache while decode rows take their next token in the SAME
    compiled step — decode throughput is never gated on a prefill finishing
    (the request-level {Load | Cal | Store} overlap of §V-A).

    Returns (logits (B, vocab) at each row's LAST valid token — the sampling
    row for decode rows and for the chunk that completes a prompt — and the
    new caches).  Rows with ntok == 0 return garbage logits the engine never
    reads.  ``kv_live`` bounds the hottest row's frontier (bucketed, static).
    """
    b, c = tokens.shape
    x = embed_tokens(params, cfg, tokens, rt)
    pos = jnp.asarray(pos, jnp.int32)
    ntok = jnp.asarray(ntok, jnp.int32)
    positions = pos[:, None] + jnp.arange(c, dtype=jnp.int32)  # (B, C)
    x = _boundary(x, rt, cfg)
    x, new_caches, _ = run_stack(
        params["layers"], cfg, x, rt, slots=cfg.period_slots, mode="mixed",
        positions=positions, caches=caches, pos=pos, causal=cfg.causal,
        kv_live=kv_live, ntok=ntok, page_table=page_table, page=page,
        cross_table=cross_table,
    )
    nf = jax.tree.map(lambda a: a[0], params["final_norm"])
    x = _norm(nf, cfg, x)
    idx = jnp.clip(ntok - 1, 0, c - 1)
    last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
    logits = last @ params["head"].astype(x.dtype)
    return logits, new_caches
