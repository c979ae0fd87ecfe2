"""Compile rehearsals: the main-path Pallas kernels at qwen3-0.6b widths,
compiled for a DESCRIBED TPU v5e chip (nothing runs).

Interpret mode cannot see what Mosaic refuses — block shapes off the (8, 128)
tiling, VMEM overruns, unsupported dtype casts — so every kernel the paged
serve path and the fused BPMM linear layer launch is compiled here at the
shapes ``qwen3-0.6b`` serving builds: head_dim 128, 8 KV heads over 16 query
heads, 128-token pages, a 256-page pool (batch 8 x cache_len 4096), chunk
256, and the BPMM factor grids nb = b = 32.

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
from test_pool_inplace import paged_lowerings, stacked_pool_ops

KV, G, HD, PAGE = 8, 2, 128, 128  # qwen3-0.6b: 16 q heads over 8 kv heads
N_PAGES = 256  # batch 8 x cache_len 4096 / page
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
    """K/V pools in kernel layout (KV, n_pages * page, D) plus, for a
    quantized pool, the (n_pages, KV, 1, page) f32 scale layout."""
    store = quant.kv_store_dtype(kv_dtype, jnp.bfloat16)
    k = _spec(sh, (KV, N_PAGES * PAGE, HD), store)
    scales = {}
    if kv_dtype != "bf16":
        s = _spec(sh, (N_PAGES, KV, 1, PAGE), jnp.float32)
        scales = {"k_scale": s, "v_scale": s}
    return k, k, scales


def _compiled_text(lowered) -> str:
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the compiled module"
    return text


@pytest.mark.parametrize("kv_dtype", KV_DTYPES)
def test_decode_paged_compiles(one_chip, kv_dtype):
    k, v, scales = _pool(one_chip, kv_dtype)
    tab = _spec(one_chip, (BATCH, MAX_LIVE), jnp.int32)
    _compiled_text(fa.mha_decode_paged.lower(
        _spec(one_chip, (BATCH, KV, 8, HD), jnp.bfloat16), k, v,
        _spec(one_chip, (BATCH,), jnp.int32), tab, tab, tab,
        scale=SCALE, window=None, kv_tile=PAGE, interpret=False, **scales,
    ))


@pytest.mark.parametrize("kv_dtype", KV_DTYPES)
@pytest.mark.parametrize("pattern", ["dense", "butterfly"])
def test_chunk_paged_compiles(one_chip, pattern, kv_dtype):
    k, v, scales = _pool(one_chip, kv_dtype)
    tab = _spec(one_chip, (1, MAX_LIVE), jnp.int32)
    _compiled_text(fa.mha_chunk_paged.lower(
        _spec(one_chip, (1, KV, G, CHUNK, HD), jnp.bfloat16), k, v,
        _spec(one_chip, (1,), jnp.int32), tab, tab, tab,
        scale=SCALE, window=None, s_kv=CACHE_LEN, q_tile=128, kv_tile=PAGE,
        pattern=pattern, interpret=False, **scales,
    ))


@pytest.mark.parametrize("kv_dtype", KV_DTYPES)
def test_prefill_paged_compiles(one_chip, kv_dtype):
    k, v, scales = _pool(one_chip, kv_dtype)
    tab = _spec(one_chip, (PREFILL_S // 128, PREFILL_S // PAGE), jnp.int32)
    _compiled_text(fa.mha_prefill.lower(
        _spec(one_chip, (KV, G, PREFILL_S, HD), jnp.bfloat16), k, v, tab, tab,
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
    widths, two layers, the cells' fused kernels): the layer scan carries
    the stacked pool and scatters into it, so no copy, broadcast,
    dynamic-update-slice or AllocateBuffer of the stacked pool's shape is
    left in the compiled module.  The int8 pool's f32 scale leaves, [L,
    rows, KV] and a sixteenth of the K/V bytes, are left out: at this
    test's pool size XLA stages them through VMEM with a layout copy on the
    way in and out (at the cells' 260-page pool it does not, in decode and
    chunk)."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    n_layers, n_pages = 2, 64
    cfg = dataclasses.replace(
        registry.get("qwen3-0.6b+flash+butterfly_attn"), n_layers=n_layers
    )
    lower = paged_lowerings(
        cfg, kv_dtype, n_pages, chunk=CHUNK, batch=BATCH, prompt=512,
        device=next(iter(one_chip.device_set)),
    )[program]
    hlo = _compiled_text(lower())
    assert stacked_pool_ops(hlo, n_layers, n_pages * PAGE, KV, HD) == []
