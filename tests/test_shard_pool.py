"""Mesh-sharded page pool: the host allocator's shard accounting, the
mesh-local table translation, and the per-shard gather reassembly.

The invariant everything here pins: GSPMD partitions the device pool's page
axis into contiguous ranges, the host :class:`PagePool` shards its free
lists over the SAME ranges, and every allocated page is owned by exactly
one shard — so masked-and-rebased per-shard translations partition the
replicated liveness, and summing per-shard gathers reassembles the
replicated gather bit-exactly."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import sparsity  # noqa: E402
from repro.launch.serve import PagePool  # noqa: E402
from repro.models.layers import gather_pages  # noqa: E402

# (pattern, pattern_arg/window, cache_len, page) — dense-causal, sliding
# window, and the paper's butterfly, at an uneven tail tile
TABLE_SWEEP = [
    ("causal", None, 256, 32),
    ("window", 64, 256, 32),
    ("butterfly", None, 512, 64),
    ("causal", None, 240, 32),  # ragged: cache_len not a tile multiple
]


def _random_tables(rng, B, n_vtiles, n_pages):
    """Page tables with sentinels (unallocated tails) and cross-row aliasing
    (prefix sharing), each allocated id drawn without replacement so pages
    are owned once — matching the allocator's contract."""
    pt = np.full((B, n_vtiles), n_pages, np.int32)
    perm = rng.permutation(n_pages)
    k = 0
    shared = perm[k]; k += 1  # one page aliased by every row (radix hit)
    for b in range(B):
        n_alloc = rng.integers(1, n_vtiles + 1)
        pt[b, 0] = shared
        for t in range(1, n_alloc):
            pt[b, t] = perm[k]
            k += 1
    return pt


@pytest.mark.parametrize("pattern,arg,cache_len,page", TABLE_SWEEP)
@pytest.mark.parametrize("n_shards", [2, 4])
def test_sharded_translate_partitions_replicated(
    pattern, arg, cache_len, page, n_shards
):
    """Per-shard translate_tables masks + rebases such that the live entries
    across shards PARTITION the replicated live entries, with physical ids
    rebased by exactly the shard base."""
    rng = np.random.default_rng(42)
    B = 3
    n_vtiles = -(-cache_len // page)
    n_pages = ((B * n_vtiles + 4) // n_shards + 1) * n_shards
    pt = _random_tables(rng, B, n_vtiles, n_pages)
    cur = jnp.asarray(
        rng.integers(1, cache_len + 1, size=B), jnp.int32
    )
    window = arg if pattern == "window" else None
    kvi, lv = sparsity.decode_live_tables(
        pattern, cur, cache_len, page, page,
        window=window, pattern_arg=None if pattern == "window" else arg,
    )
    phys_r, virt_r, live_r = sparsity.translate_tables(
        kvi, lv, jnp.asarray(pt), n_pages
    )
    phys_r, live_r = np.asarray(phys_r), np.asarray(live_r)
    pps = n_pages // n_shards
    live_sum = np.zeros_like(live_r)
    for s in range(n_shards):
        lo, hi = s * pps, (s + 1) * pps
        phys_s, virt_s, live_s = sparsity.translate_tables(
            kvi, lv, jnp.asarray(pt), n_pages, page_range=(lo, hi)
        )
        phys_s, live_s = np.asarray(phys_s), np.asarray(live_s)
        np.testing.assert_array_equal(np.asarray(virt_s), np.asarray(virt_r))
        # a shard's live entries are replicated-live AND in its range
        assert ((live_s == 1) <= (live_r == 1)).all()
        sel = live_s == 1
        assert (phys_s[sel] + lo == phys_r[sel]).all()
        assert (phys_s[sel] >= 0).all() and (phys_s[sel] < pps).all()
        live_sum += live_s
    # each replicated-live entry owned by exactly ONE shard, none by two
    np.testing.assert_array_equal(live_sum, live_r)


@pytest.mark.parametrize("n_shards", [2, 4])
@pytest.mark.parametrize("kv_heads", [1, 2])  # MHA and GQA-shaped pools
def test_sharded_gather_reassembles_replicated(n_shards, kv_heads):
    """Sum of mesh-local gathers over per-shard sub-pools == the replicated
    gather on every ALLOCATED row (unallocated rows gather clamped garbage
    replicated-side, zeros shard-side — every consumer masks them)."""
    rng = np.random.default_rng(7)
    B, page, n_vtiles, hd = 3, 16, 6, 8
    n_pages = ((B * n_vtiles + 2) // n_shards + 1) * n_shards
    # a two-layer stack in the stored layout (L, KV, rows, hd), read at
    # layer 1; a shard holds every layer's rows of its page range
    pool = jnp.asarray(
        rng.normal(size=(2, kv_heads, n_pages * page, hd)).astype(np.float32)
    )
    pt = _random_tables(rng, B, n_vtiles, n_pages)
    n_rows = n_vtiles * page
    rep = np.asarray(gather_pages(pool, jnp.asarray(pt), n_rows, page, layer=1))
    pps = n_pages // n_shards
    acc = np.zeros_like(rep)
    for s in range(n_shards):
        lo, hi = s * pps, (s + 1) * pps
        local = pool[:, :, lo * page : hi * page]
        acc += np.asarray(
            gather_pages(
                local, jnp.asarray(pt), n_rows, page, page_range=(lo, hi),
                layer=1,
            )
        )
    alloc_rows = pt[:, np.arange(n_rows) // page] != n_pages  # (B, n_rows)
    np.testing.assert_allclose(acc[alloc_rows], rep[alloc_rows], rtol=0, atol=0)
    # unowned rows contribute exactly zero from every shard
    assert (acc[~alloc_rows] == 0).all()


def test_page_residency_per_shard_ceil():
    last = np.asarray([10, 20, 30, 40, 50, 60, 70, 80])
    res = sparsity.page_residency(last, 81, 10)
    res4 = sparsity.page_residency(last, 81, 10, n_shards=4)
    np.testing.assert_array_equal(res4, -(-res // 4))


# -- the sharded host allocator ------------------------------------------


def test_pool_shard_ranges_and_balance():
    pool = PagePool(16, n_shards=4)
    assert pool.pages_per_shard == 4
    pids = [pool.alloc(f"r{i}") for i in range(8)]
    # balanced placement: 8 pages over 4 shards -> exactly 2 per shard
    assert pool.shard_in_use == [2, 2, 2, 2]
    for pid in pids:
        assert pool.shard_of(pid) == pid // 4
    for pid in pids:
        pool.release(pid)
    assert pool.in_use == 0
    assert pool.shard_in_use == [0, 0, 0, 0]
    assert pool.shard_peak_in_use == [2, 2, 2, 2]
    assert pool.peak_in_use == 8


def test_pool_shard_peak_bound_under_churn():
    """Random alloc/release churn: balanced placement keeps every shard's
    peak within ceil(global peak / n_shards) + 1."""
    rng = np.random.default_rng(3)
    pool = PagePool(32, n_shards=4)
    held = []
    for _ in range(500):
        if held and (len(held) >= 32 or rng.random() < 0.45):
            pool.release(held.pop(rng.integers(len(held))))
        else:
            held.append(pool.alloc())
    bound = -(-pool.peak_in_use // 4) + 1
    assert max(pool.shard_peak_in_use) <= bound
    for p in held:
        pool.release(p)
    pool.close()


def test_pool_rejects_uneven_shards():
    with pytest.raises(ValueError, match="do not split"):
        PagePool(10, n_shards=4)


def test_pool_one_shard_is_flat_lifo():
    """1-shard pools must stay bit-identical to the historical flat free
    list (page 0 first) — token-level engine tests depend on the ids."""
    pool = PagePool(4)
    assert [pool.alloc() for _ in range(4)] == [0, 1, 2, 3]


def test_transfer_moves_label_not_refcount():
    pool = PagePool(4, n_shards=2)
    pid = pool.alloc("prefill:req1")
    pool.transfer(pid, "prefill:req1", "decode:req1")
    assert pool.page_refs(pid) == 1
    assert pool.holders() == {"decode:req1": 1}
    with pytest.raises(ValueError, match="holds no reference"):
        pool.transfer(pid, "prefill:req1", "decode:req1")
    pool.release(pid, "decode:req1")
    pool.close()


def test_close_leak_report_names_holders():
    pool = PagePool(8, n_shards=2)
    a = pool.alloc("req1")
    b = pool.alloc("req2")
    pool.retain(b, "radix")
    with pytest.raises(RuntimeError) as e:
        pool.close(context="end of test")
    msg = str(e.value)
    assert "end of test" in msg
    assert "'req1': 1" in msg and "'req2': 1" in msg and "'radix': 1" in msg
    pool.release(a, "req1")
    pool.release(b, "req2")
    pool.release(b, "radix")
    pool.close()  # drained: returns quietly
