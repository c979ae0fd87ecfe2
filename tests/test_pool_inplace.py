"""The paged KV pool rides the layer scan's carry and is written in place.

Two contracts of :func:`repro.models.transformer.run_stack` on the paged path:

* no compiled paged program (decode wave, prompt chunk, admission prefill)
  materialises a second buffer of a stacked pool leaf's shape (K/V ``[L,
  KV, rows, hd]``, scales ``[L, KV, n_pages, 1, page]``) — no per-layer
  restack into a fresh stacked buffer (``dynamic-update-slice``
  into an ``AllocateBuffer``), no ``broadcast`` to seed one, no whole-pool
  ``copy`` after the loop.  The new rows scatter straight into the donated
  pool.
* the carried scan computes bit for bit what the per-layer slicing reference
  (``cfg.unroll_layers``: slice layer i's pool, write it, restack) computes:
  the same logits and the same bytes in every pool leaf at every layer.

:func:`stacked_pool_ops`, :func:`layer_pool_ops` and :func:`paged_lowerings`
are shared with ``test_chip_compile.py``, which asserts the first contract
for a described TPU v5e, and there also that no program makes a buffer of
one layer's pool: the kernels read layer ``i`` where it is stored.
"""

from __future__ import annotations

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core import quant
from repro.distributed import sharding as shd
from repro.launch.mesh import make_mesh
from repro.launch.serving.entries import make_paged_fns
from repro.models import model as M
from repro.models import transformer as tf

PAGE = 128
KV, HD = 8, 128  # qwen3-0.6b's kv heads and head width
N_VT = 8  # virtual tiles per page-table row: a 1,024-token cache
KV_LIVE = N_VT * PAGE


def stacked_pool_ops(hlo: str, *dims: int) -> list[str]:
    """Instructions of an optimized HLO module that make a new buffer of a
    stacked pool leaf's shape, a shape that starts with ``dims`` (``L, KV,
    rows`` matches the K/V leaves, ``L, KV, n_pages, 1`` the scale leaves):
    ``copy`` / ``copy-start``, ``broadcast``, ``dynamic-update-slice`` and
    custom calls (``AllocateBuffer``).  An in-place scatter into the carried
    pool is none of these."""
    op = re.compile(
        r"= \w+\[%s(,\d+)*\]\S* "
        r"(copy|copy-start|broadcast|dynamic-update-slice|custom-call)\("
        % ",".join(map(str, dims))
    )
    return [ln.strip()[:200] for ln in hlo.splitlines() if op.search(ln)]


def layer_pool_ops(hlo: str, rows: int, kv: int, hd: int) -> list[str]:
    """Instructions of an optimized HLO module that make a buffer of ONE
    layer's K/V pool, in the stored layout or the one before it: ``[1, KV,
    rows, hd]`` / ``[KV, rows, hd]`` (a layer of the stack, as a kernel
    reads it) or ``[1, rows, KV, hd]`` / ``[rows, KV, hd]`` — made by a
    ``(dynamic-)slice``, ``copy``, ``transpose`` or fusion, or the end of an
    async slice or copy.  A kernel that reads the stacked pool in place
    makes none."""
    shapes = ((1, kv, rows, hd), (kv, rows, hd), (1, rows, kv, hd), (rows, kv, hd))
    op = re.compile(
        r"= \w+\[(%s)\]\S* "
        r"(dynamic-slice|slice|slice-done|copy|copy-done|transpose|fusion)\("
        % "|".join(",".join(map(str, s)) for s in shapes)
    )
    return [ln.strip()[:200] for ln in hlo.splitlines() if op.search(ln)]


def paged_lowerings(cfg, kv_dtype: str, n_pages: int, *, chunk: int,
                    batch: int, prompt: int, device=None, mesh=None) -> dict:
    """``{program: thunk}``: each thunk lowers one of the paged entry points
    (decode wave, prompt chunk, admission prefill) of ``make_paged_fns`` on
    abstract parameters and pools, committed to ``device`` when given, or
    laid out over ``mesh`` (a ``pages`` axis shards the pool) by the entry
    points' own shardings."""
    if mesh is None:
        devices = [device] if device is not None else jax.devices()[:1]
        mesh = make_mesh((1, 1), ("data", "model"), devices=devices)
    pre, dec, chk, _, _ = make_paged_fns(
        cfg, mesh, n_pages=n_pages, page=PAGE, chunk=chunk, kv_dtype=kv_dtype
    )
    sh = None if device is None else jax.sharding.SingleDeviceSharding(device)
    store = quant.kv_store_dtype(kv_dtype, jnp.dtype(cfg.dtype))

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sh)

    def pool_leaf(path, s):
        scale = path[-1].key.endswith("_scale")
        return sds(s.shape, jnp.float32 if scale else store)

    is_spec = lambda x: isinstance(x, shd.ParamSpec)  # noqa: E731
    params = jax.tree.map(
        lambda s: sds(s.shape, cfg.param_dtype), M.build_specs(cfg),
        is_leaf=is_spec,
    )
    pools = jax.tree_util.tree_map_with_path(
        pool_leaf, tf.paged_pool_specs(cfg, n_pages, PAGE, kv_dtype=kv_dtype),
        is_leaf=is_spec,
    )
    return {
        "decode": lambda: dec.jit_for(KV_LIVE).lower(
            params, pools, sds((batch, 1)), sds((batch,)), sds((batch, N_VT))
        ),
        "chunk": lambda: chk.jit_for(KV_LIVE).lower(
            params, pools, sds((1, chunk)), sds((1, N_VT)), sds(()), sds(())
        ),
        "prefill": lambda: pre.lower(
            params, pools, {"tokens": sds((1, prompt))}, sds((1,)),
            sds((1, N_VT)),
        ),
    }


# --------------------------------------------------------------------------
# Compiled programs: no stacked-shape buffer besides the donated pool
# --------------------------------------------------------------------------

N_LAYERS, N_PAGES = 2, 16


@pytest.mark.parametrize("program", ["decode", "chunk", "prefill"])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_programs_write_pool_in_place(kv_dtype, program):
    # qwen3-0.6b at published widths, two layers, the cells' fused kernels
    cfg = dataclasses.replace(
        registry.get("qwen3-0.6b+flash+butterfly_attn"), n_layers=N_LAYERS
    )
    lower = paged_lowerings(
        cfg, kv_dtype, N_PAGES, chunk=128, batch=4, prompt=256
    )[program]
    hlo = lower().compile().as_text()
    assert "scatter" in hlo
    assert stacked_pool_ops(hlo, N_LAYERS, KV, N_PAGES * PAGE) == []
    assert stacked_pool_ops(hlo, N_LAYERS, KV, N_PAGES, 1) == []


def test_stacked_pool_ops_finds_restack_and_copies():
    """The matcher sees the restack and the whole-pool copies it guards
    against: a scan that passes a stacked pool through ``xs``/``ys``."""
    pool = jnp.zeros((2, 2, 256, 128), jnp.bfloat16)

    def restack(pool, x):
        def body(c, p):
            p = p.at[:, 0].set(c)
            return c, p

        return jax.lax.scan(body, x, pool)[1]

    hlo = (
        jax.jit(restack)
        .lower(pool, jnp.ones((2, 128), jnp.bfloat16))
        .compile()
        .as_text()
    )
    assert stacked_pool_ops(hlo, 2, 2, 256)


def test_layer_pool_ops_finds_layer_slices():
    """The one-layer matcher sees the per-call layer moves it guards
    against: the slice and transpose ops of the pool layout the kernels no
    longer need (as the optimized TPU modules named them), and a compiled
    slice of one layer out of a stacked pool; not the in-place scatter or
    a bitcast of the stacked pool."""
    rows, t = 36864, "{3,2,1,0:T(8,128)(2,1)}"
    lines = [
        f"%constant_dynamic-slice_fusion.14 = bf16[1,{rows},8,128]{t} "
        "fusion(%p0, %i), kind=kLoop",
        f"%copy_bitcast_fusion.4 = bf16[8,{rows},128]{{2,1,0}} fusion(%a), kind=kLoop",
        f"%slice-done.2 = bf16[1,8,{rows},128]{t} slice-done(%slice-start.2)",
        f"%scatter.3 = bf16[28,8,{rows},128]{t} scatter(%p, %i, %u), "
        "update_window_dims={2}",
        f"%bitcast.5 = bf16[28,8,{rows},128]{t} bitcast(%f)",
    ]
    found = layer_pool_ops("\n".join(lines), rows, 8, 128)
    assert [ln.split(" =")[0] for ln in found] == [
        "%constant_dynamic-slice_fusion.14", "%copy_bitcast_fusion.4",
        "%slice-done.2",
    ]

    def one_layer(pool, i):
        return jax.lax.dynamic_index_in_dim(pool, i, keepdims=False) * 2

    pool = jnp.zeros((3, 2, 256, 128), jnp.bfloat16)
    hlo = jax.jit(one_layer).lower(pool, 1).compile().as_text()
    assert layer_pool_ops(hlo, 256, 2, 128)


# --------------------------------------------------------------------------
# Parity: carried scan == per-layer slicing reference, bit for bit
# --------------------------------------------------------------------------

N_POOL_PAGES = 20


def _pools(cfg, kv_dtype):
    """Stacked pools filled with random rows (and, quantized, random
    scales), so every read and every untouched row is checked."""
    specs = tf.paged_pool_specs(cfg, N_POOL_PAGES, PAGE, kv_dtype=kv_dtype)
    store = quant.kv_store_dtype(kv_dtype, jnp.dtype(cfg.dtype))
    leaves, tree = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, shd.ParamSpec)
    )
    keys = jax.random.split(jax.random.PRNGKey(0), len(leaves))
    out = []
    for (path, s), k in zip(leaves, keys):
        if path[-1].key.endswith("_scale"):
            a = jax.random.uniform(k, s.shape, jnp.float32, 0.005, 0.02)
        elif store == jnp.int8:
            a = jax.random.randint(k, s.shape, -127, 128).astype(jnp.int8)
        else:
            a = jax.random.normal(k, s.shape, jnp.float32).astype(store)
        out.append(a)
    return jax.tree_util.tree_unflatten(tree, out)


def _bits(a):
    a = np.asarray(a)
    return a.view(np.dtype(f"u{a.dtype.itemsize}"))


def _run(cfg, kv_dtype, params, program):
    """One decode wave or one prompt chunk on fresh random pools.  Both
    programs compile with ``xla_allow_excess_precision`` off: XLA may
    otherwise keep a bf16 intermediate in f32 in the unrolled program and
    not in the scanned one, a difference of the compiler, not of the pool
    handling under test."""
    _, dec, chk, _, _ = make_paged_fns(
        cfg, make_mesh((1, 1), ("data", "model")), n_pages=N_POOL_PAGES,
        page=PAGE, chunk=128, kv_dtype=kv_dtype,
    )
    # row 0 owns pages 0..7 out of order, row 1 pages 8..13 (tiles 6 and 7
    # unallocated): every write goes through a non-trivial indirection
    pt = jnp.asarray(
        [[3, 0, 7, 1, 6, 2, 5, 4], [8, 9, 10, 11, 12, 13, 20, 20]], jnp.int32
    )
    toks = jax.random.randint(jax.random.PRNGKey(7), (2, 128), 0, cfg.vocab)
    pools = _pools(cfg, kv_dtype)
    if program == "decode":
        fn = dec.jit_for(KV_LIVE)
        args = (params, pools, toks[:, :1], jnp.asarray([700, 530], jnp.int32), pt)
    else:
        fn = chk.jit_for(KV_LIVE)
        args = (params, pools, toks[:1], pt[:1], jnp.int32(512), jnp.int32(100))
    exact = {"xla_allow_excess_precision": False}
    return fn.lower(*args).compile(exact)(*args)


@pytest.mark.parametrize("pattern", ["dense", "butterfly"])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_carried_pool_matches_sliced_reference(kv_dtype, pattern):
    arch = "qwen3-0.6b+flash" + ("+butterfly_attn" if pattern != "dense" else "")
    cfg = registry.get(arch, reduced=True)
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    before = _pools(cfg, kv_dtype)
    n_layers = cfg.n_periods
    for program in ("decode", "chunk"):
        logits, pools = _run(cfg, kv_dtype, params, program)
        ref_logits, ref_pools = _run(
            dataclasses.replace(cfg, unroll_layers=True), kv_dtype, params,
            program,
        )
        np.testing.assert_array_equal(_bits(logits), _bits(ref_logits))
        leaves = jax.tree_util.tree_leaves_with_path(pools)
        ref = dict(jax.tree_util.tree_leaves_with_path(ref_pools))
        old = dict(jax.tree_util.tree_leaves_with_path(before))
        want = {"k", "v"} | ({"k_scale", "v_scale"} if kv_dtype != "bf16" else set())
        assert {p[-1].key for p, _ in leaves} == want
        for path, leaf in leaves:
            assert leaf.shape[0] == n_layers
            for i in range(n_layers):
                got, exp = _bits(leaf[i]), _bits(ref[path][i])
                np.testing.assert_array_equal(
                    got, exp, err_msg=f"{program} {jax.tree_util.keystr(path)} layer {i}"
                )
                # the call wrote this layer (not a vacuous match of two
                # untouched pools)
                assert not np.array_equal(got, _bits(old[path][i]))

