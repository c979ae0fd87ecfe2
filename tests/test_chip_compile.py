"""Compile rehearsals: the main-path Pallas kernels at qwen3-0.6b widths,
compiled for a DESCRIBED TPU v5e chip (nothing runs).

Interpret mode cannot see what Mosaic refuses — block shapes off the (8, 128)
tiling, VMEM overruns, unsupported dtype casts — so every kernel the paged
serve path and the fused BPMM linear layer launch is compiled here at the
shapes ``qwen3-0.6b`` serving builds: head_dim 128, 8 KV heads over 16 query
heads, 128-token pages, a 256-page pool (batch 8 x cache_len 4096) stacked
over two layers and read at a traced layer, chunk 256, and the BPMM factor
grids nb = b = 32.

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU library, and the worker that is handed this
file keeps it until it exits.  The ``fa.*`` kernels are called with
``interpret=False`` directly because ``ops._interpret()`` sees the CPU.
"""

from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.core import quant
from repro.kernels import flash_attention as fa
from repro.kernels import monarch_bpmm as mk
from repro.kernels import ops
from repro.launch.mesh import make_mesh
from test_pool_inplace import layer_pool_ops, paged_lowerings, stacked_pool_ops

KV, G, HD, PAGE = 8, 2, 128, 128  # qwen3-0.6b: 16 q heads over 8 kv heads
N_PAGES = 256  # batch 8 x cache_len 4096 / page
N_LAYERS = 2
CACHE_LEN = 4096
CHUNK = 256
BATCH = 8
PREFILL_S = 2048
MAX_LIVE = CACHE_LEN // PAGE  # widest possible table: every tile live
SCALE = HD ** -0.5
KV_DTYPES = ["bf16", "int8", "fp8_e4m3"]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described chip's executables cannot be read back from a persistent
    # cache, so keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sh, shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sh)


def _pool(sh, kv_dtype):
    """K/V pools as stored, stacked by layer (L, KV, n_pages * page, D), a
    traced layer index, and, for a quantized pool, the stacked (L, KV,
    n_pages, 1, page) f32 scale leaves."""
    store = quant.kv_store_dtype(kv_dtype, jnp.bfloat16)
    k = _spec(sh, (N_LAYERS, KV, N_PAGES * PAGE, HD), store)
    scales = {}
    if kv_dtype != "bf16":
        s = _spec(sh, (N_LAYERS, KV, N_PAGES, 1, PAGE), jnp.float32)
        scales = {"k_scale": s, "v_scale": s}
    return k, k, _spec(sh, (), jnp.int32), scales


def _compiled_text(lowered) -> str:
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the compiled module"
    return text


@pytest.mark.parametrize("kv_dtype", KV_DTYPES)
def test_decode_paged_compiles(one_chip, kv_dtype):
    k, v, layer, scales = _pool(one_chip, kv_dtype)
    tab = _spec(one_chip, (BATCH, MAX_LIVE), jnp.int32)
    _compiled_text(fa.mha_decode_paged.lower(
        _spec(one_chip, (BATCH, KV, 8, HD), jnp.bfloat16), k, v, layer,
        _spec(one_chip, (BATCH,), jnp.int32), tab, tab, tab,
        scale=SCALE, window=None, kv_tile=PAGE, interpret=False, **scales,
    ))


@pytest.mark.parametrize("kv_dtype", KV_DTYPES)
@pytest.mark.parametrize("pattern", ["dense", "butterfly"])
def test_chunk_paged_compiles(one_chip, pattern, kv_dtype):
    k, v, layer, scales = _pool(one_chip, kv_dtype)
    tab = _spec(one_chip, (1, MAX_LIVE), jnp.int32)
    _compiled_text(fa.mha_chunk_paged.lower(
        _spec(one_chip, (1, KV, G, CHUNK, HD), jnp.bfloat16), k, v, layer,
        _spec(one_chip, (1,), jnp.int32), tab, tab, tab,
        scale=SCALE, window=None, s_kv=CACHE_LEN, q_tile=128, kv_tile=PAGE,
        pattern=pattern, interpret=False, **scales,
    ))


@pytest.mark.parametrize("kv_dtype", KV_DTYPES)
def test_prefill_paged_compiles(one_chip, kv_dtype):
    k, v, layer, scales = _pool(one_chip, kv_dtype)
    tab = _spec(one_chip, (PREFILL_S // 128, PREFILL_S // PAGE), jnp.int32)
    _compiled_text(fa.mha_prefill.lower(
        _spec(one_chip, (KV, G, PREFILL_S, HD), jnp.bfloat16), k, v, layer,
        tab, tab,
        scale=SCALE, causal=True, window=None, s_q=PREFILL_S, s_kv=PREFILL_S,
        q_tile=128, kv_tile=PAGE, interpret=False, kv_virt=tab, **scales,
    ))


# (gin, gout) of every qwen3-0.6b BPMM factor: wk/wv (1,1), wq (1,2),
# wo (2,1), w1/w3 (1,3), w2 (3,1) — d_model 1024 = 32 x 32 per slice
@pytest.mark.parametrize("gin,gout", [(1, 1), (2, 1), (1, 2), (1, 3), (3, 1)])
def test_monarch_bpmm_compiles(one_chip, gin, gout):
    nb = b = 32
    tile = mk.pick_token_tile(gin, nb, b, dtype_bytes=2)
    t = 4 * tile
    _compiled_text(mk.monarch_bpmm.lower(
        _spec(one_chip, (t, gin, nb, b), jnp.bfloat16),
        _spec(one_chip, (gout, gin, nb, b, b), jnp.bfloat16),
        _spec(one_chip, (gout, gin, b, nb, nb), jnp.bfloat16),
        token_tile=tile, interpret=False,
    ))


@pytest.mark.parametrize("program", ["decode", "chunk", "prefill"])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_programs_write_pool_in_place(one_chip, monkeypatch, kv_dtype,
                                            program):
    """Whole paged serve programs for the chip (qwen3-0.6b at published
    widths, two layers, the cells' fused kernels, a 256-page pool): the
    layer scan carries the stacked pool and scatters into it, so no copy,
    broadcast, dynamic-update-slice or AllocateBuffer of the stacked K/V
    pool's shape is left in the compiled module; and the kernels read layer
    ``i`` where it is stored, so no op makes a buffer of one layer's pool
    in either layout ([1, KV, rows, hd], [KV, rows, hd], [1, rows, KV, hd],
    [rows, KV, hd]).

    The pool is 256 pages and not 64 because at two layers a 64-page leaf
    is 32 MiB, which fits in the chip's VMEM whole: XLA then stages the
    stacked K/V leaves there each call, a slice of each layer joined by a
    ConcatBitcast (prefill, and every int8 program).  A real pool's leaf is
    at least a GiB (28 layers; the next test holds the smallest cell's), so
    that staging is a property of the toy size.  The int8 pool's f32 scale
    leaves, [L, KV, n_pages, 1, page], are left out for the same reason:
    they are a thirty-second of the K/V bytes, and XLA stages them through
    VMEM at every size, the cells' included."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = dataclasses.replace(
        registry.get("qwen3-0.6b+flash+butterfly_attn"), n_layers=N_LAYERS
    )
    lower = paged_lowerings(
        cfg, kv_dtype, N_PAGES, chunk=CHUNK, batch=BATCH, prompt=512,
        device=next(iter(one_chip.device_set)),
    )[program]
    hlo = _compiled_text(lower())
    assert stacked_pool_ops(hlo, N_LAYERS, KV, N_PAGES * PAGE, HD) == []
    assert layer_pool_ops(hlo, N_PAGES * PAGE, KV, HD) == []


@pytest.mark.parametrize("program", ["decode", "chunk", "prefill"])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_programs_write_pool_in_place_at_cell_size(
    one_chip, monkeypatch, kv_dtype, program
):
    """The same contract at the depth and pool of the smallest cell,
    ``qwen3-bpmm.prefill``: 28 layers of BPMM linears and a 144-page pool,
    whose one-layer kernel-layout copies (36 MiB each) were small enough for
    XLA to keep in VMEM before the pool was stored in the kernels' layout.
    Now the kernels take the stacked K/V leaves from HBM, and no program
    makes a buffer of the stack or of one layer of it."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = registry.get("qwen3-0.6b+bpmm-k+flash+butterfly_attn")
    n_pages = 144
    lower = paged_lowerings(
        cfg, kv_dtype, n_pages, chunk=CHUNK, batch=BATCH, prompt=512,
        device=next(iter(one_chip.device_set)),
    )[program]
    hlo = _compiled_text(lower())
    rows = n_pages * PAGE
    assert stacked_pool_ops(hlo, cfg.n_layers, KV, rows, HD) == []
    assert layer_pool_ops(hlo, rows, KV, HD) == []


@pytest.fixture(scope="module")
def pages_mesh(topo, one_chip):
    """The 2x2 chips as one 4-way ``pages`` axis (``make_pages_mesh(4)``'s
    layout), with the compilation cache off as for ``one_chip``."""
    return make_mesh((1, 1, 4), ("data", "model", "pages"), devices=topo.devices)


@pytest.mark.parametrize("program", ["decode", "chunk", "prefill"])
def test_pages_mesh_programs_move_no_pool(pages_mesh, program):
    """Paged serve programs over a 4-way ``pages`` mesh, the decode side of
    ``DisaggRouter`` (qwen3-0.6b, 28 layers, XLA attention, 4 x 260 pages):
    each chip's shard of the pool stays where it is.  No all-gather, and no
    buffer of the shard's stacked shape or of one layer of it: the XLA
    forms gather and scatter rows of hd at (layer, head, row) in place."""
    cfg = registry.get("qwen3-0.6b")
    shard_pages = 260
    lower = paged_lowerings(
        cfg, "bf16", 4 * shard_pages, chunk=CHUNK, batch=BATCH, prompt=512,
        mesh=pages_mesh,
    )[program]
    hlo = lower().compile().as_text()
    assert "all-gather" not in hlo
    rows = shard_pages * PAGE
    assert stacked_pool_ops(hlo, cfg.n_layers, KV, rows, HD) == []
    assert layer_pool_ops(hlo, rows, KV, HD) == []
