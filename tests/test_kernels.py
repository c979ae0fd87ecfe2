"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret=True)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import api, fft_mixing as fm
from repro.kernels import fft2d, monarch_bpmm as mk, ops, ref


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "t,gin,gout,nb,b",
    [(16, 1, 1, 4, 8), (32, 2, 3, 8, 16), (8, 1, 2, 16, 32), (24, 3, 1, 2, 64)],
)
def test_monarch_kernel_sweep(t, gin, gout, nb, b, dtype):
    key = jax.random.PRNGKey(t + nb)
    ks = jax.random.split(key, 3)
    x = jax.random.normal(ks[0], (t, gin, nb, b), dtype)
    r = (jax.random.normal(ks[1], (gout, gin, nb, b, b), jnp.float32) / np.sqrt(b)).astype(dtype)
    l = (jax.random.normal(ks[2], (gout, gin, b, nb, nb), jnp.float32) / np.sqrt(nb)).astype(dtype)
    y = mk.monarch_bpmm(x, r, l, token_tile=8, interpret=True)
    y_ref = ref.monarch_bpmm_ref(x, r, l)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(y_ref, np.float32), rtol=tol, atol=tol
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("complex_in", [False, True])
@pytest.mark.parametrize("t,n1,n2", [(8, 4, 8), (16, 16, 16), (8, 8, 32), (8, 32, 8)])
def test_fft_kernel_sweep(t, n1, n2, complex_in, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (t, n1 * n2), dtype)
    xi = jax.random.normal(jax.random.PRNGKey(1), (t, n1 * n2), dtype) if complex_in else None
    yr, yi = fft2d.dft_two_stage(x, xi, n1=n1, n2=n2, token_tile=8, interpret=True)
    rr, ri = ref.dft_two_stage_ref(x.astype(jnp.float32), None if xi is None else xi.astype(jnp.float32))
    scale = float(jnp.max(jnp.abs(rr))) + 1e-6
    tol = 1e-4 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(yr, np.float32), np.asarray(rr), rtol=tol, atol=tol * scale)
    np.testing.assert_allclose(np.asarray(yi, np.float32), np.asarray(ri), rtol=tol, atol=tol * scale)


def test_ops_monarch_linear_matches_einsum_path():
    spec_e = api.LinearSpec(100, 300, "monarch", max_block=32)
    spec_k = api.LinearSpec(100, 300, "monarch_kernel", max_block=32)
    p = api.init_linear(jax.random.PRNGKey(3), spec_e)
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 7, 100), jnp.float32)
    y1 = api.apply_linear(p, spec_e, x)
    y2 = api.apply_linear(p, spec_k, x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("plan", [(8, 8, 8), (16, 16), (4, 8, 4, 4)])
def test_ops_dft_multistage(plan):
    n = int(np.prod(plan))
    x = jax.random.normal(jax.random.PRNGKey(5), (4, n), jnp.float32)
    yr, yi = ops.dft_1d(x, None, plan=plan)
    f = np.fft.fft(np.asarray(x), axis=-1)
    np.testing.assert_allclose(np.asarray(yr), f.real, rtol=1e-3, atol=1e-3 * np.abs(f).max())
    np.testing.assert_allclose(np.asarray(yi), f.imag, rtol=1e-3, atol=1e-3 * np.abs(f).max())


def test_fnet_kernel_matches_reference():
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 64, 96), jnp.float32)
    y = ops.fnet_mixing_kernel(x, max_radix=16)
    y_ref = fm.fnet_mixing_reference(x)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(y_ref), rtol=1e-3, atol=1e-3 * float(jnp.abs(y_ref).max())
    )


def test_fnet_staged_xla_matches_reference():
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 128, 96), jnp.float32)
    y = fm.fnet_mixing(x, max_radix=32)
    y_ref = fm.fnet_mixing_reference(x)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(y_ref), rtol=1e-3, atol=1e-3 * float(jnp.abs(y_ref).max())
    )


# --------------------------------------------------------------------------
# Paged kernels read layer i of the stacked pool where it is stored
# --------------------------------------------------------------------------

PAGED_CALLS = ["decode", "chunk", "prefill", "ring_decode", "ring_chunk"]


def _stacked_pool(kv_dtype, n_layers, kv, rows, hd, page):
    """A stacked pool in the stored layout, every layer's rows distinct:
    K/V (L, KV, rows, hd) and, quantized, scales (L, KV, n_pages, 1, page)."""
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(5), 4)
    shape = (n_layers, kv, rows, hd)
    if kv_dtype == "bf16":
        return jax.random.normal(k1, shape), jax.random.normal(k2, shape), None, None
    sshape = (n_layers, kv, rows // page, 1, page)
    return (
        jax.random.randint(k1, shape, -127, 128).astype(jnp.int8),
        jax.random.randint(k2, shape, -127, 128).astype(jnp.int8),
        jax.random.uniform(k3, sshape, jnp.float32, 0.005, 0.02),
        jax.random.uniform(k4, sshape, jnp.float32, 0.005, 0.02),
    )


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("call", PAGED_CALLS)
def test_paged_kernels_read_the_named_layer(monkeypatch, call, kv_dtype):
    """Each paged kernel, handed the whole stacked pool and layer ``i``,
    reads layer ``i`` and nothing else: its output equals, bit for bit, the
    same call on a one-layer stack holding layer ``i`` alone, and differs
    from layer to layer.  Interpret mode, with the pool passed to the
    kernel stacked, as the TPU path passes it (on the CPU ``ops`` otherwise
    hands the interpreter the one layer it reads)."""
    from repro.core.attention import AttentionSpec

    monkeypatch.setattr(ops, "_kernel_pools", lambda layer, *leaves: (layer, leaves))
    n_layers, kv, h, hd, page, n_pages = 3, 2, 4, 64, 16, 8
    kp, vp, ks, vs = _stacked_pool(kv_dtype, n_layers, kv, n_pages * page, hd, page)
    spec = AttentionSpec(impl="flash_kernel")
    q = jax.random.normal(jax.random.PRNGKey(6), (2, 3 * page, h, hd))
    # row 0 owns pages 5, 2, 7, 0; row 1 pages 1, 6, 3, 4
    pt = jnp.asarray([[5, 2, 7, 0], [1, 6, 3, 4]], jnp.int32)
    ring = dict(ring_window=20, ring_tiles=4)

    def run(k, v, k_scale, v_scale, layer):
        sc = dict(page=page, spec=spec, layer=layer, k_scale=k_scale, v_scale=v_scale)
        if call == "decode":
            return ops.flash_paged_decode(
                q[:, 0], k, v, jnp.asarray([61, 30], jnp.int32), pt, **sc
            )
        if call == "ring_decode":  # positions past the ring's span
            return ops.flash_paged_decode(
                q[:, 0], k, v, jnp.asarray([150, 97], jnp.int32), pt, **sc,
                kv_live=160, **ring,
            )
        if call == "chunk":
            return ops.flash_paged_chunk(
                q[:, :page], k, v, jnp.asarray([40, 9], jnp.int32),
                jnp.asarray([page, 5], jnp.int32), pt, **sc,
            )
        if call == "ring_chunk":
            return ops.flash_paged_chunk(
                q[:, :page], k, v, jnp.asarray([120, 70], jnp.int32),
                jnp.asarray([page, 7], jnp.int32), pt, **sc, kv_live=144, **ring,
            )
        return ops.flash_paged_prefill(q[:1], k, v, pt[0], **sc)  # 3 pages

    def one(a, i):
        return None if a is None else a[i : i + 1]

    outs = []
    for i in range(n_layers):
        got = run(kp, vp, ks, vs, i)
        want = run(one(kp, i), one(vp, i), one(ks, i), one(vs, i), 0)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(want), err_msg=f"layer {i}"
        )
        outs.append(np.asarray(got))
    for i in range(1, n_layers):
        assert not np.array_equal(outs[0], outs[i])
