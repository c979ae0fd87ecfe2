"""Radix-tree prefix cache: shared-prefix runs must be token-identical to
cold-start runs across patterns x backends x scheduling modes, CoW must
isolate sibling divergence, and eviction-then-readmit must stay correct.

The sharing contract: butterfly (and every other static) live-tile map is a
pure function of position, so prefix KV tiles are bit-identical across
requests — aliasing them through the page table changes WHICH physical rows
a request reads, never their values.  Every test therefore reduces to: same
tokens out, fewer prefill tokens / resident pages in, pool drained after.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import registry
from repro.core.attention import AttentionSpec
from repro.launch.mesh import make_local_mesh
from repro.launch.serve import PagePool, RadixCache, Request, ServeLoop
from repro.models import model as M


def _f32(cfg):
    return dataclasses.replace(cfg, dtype="float32", capacity_factor=8.0)


def _cfg(pattern="dense", arg=None, impl="xla_chunked"):
    return dataclasses.replace(
        _f32(registry.get("qwen3-0.6b", reduced=True)),
        attention=AttentionSpec(impl=impl, pattern=pattern, pattern_arg=arg),
    )


def _shared_reqs(cfg, *, prefix_len=200, suffixes=(60, 30, 45), max_new=3,
                 seed=3):
    """A donor plus siblings sharing `prefix_len` tokens, all with distinct
    suffixes — the donor's insert makes every later request a radix hit."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, cfg.vocab, size=prefix_len).astype(np.int32)
    prompts = [
        np.concatenate([shared, rng.integers(0, cfg.vocab, size=s).astype(np.int32)])
        for s in suffixes
    ]
    return [Request(uid=i, prompt=p, max_new=max_new)
            for i, p in enumerate(prompts)]


# --------------------------------------------------------------------------
# RadixCache unit behaviour
# --------------------------------------------------------------------------


def test_radix_match_insert_split():
    """Insert/match at page granularity: full-page prefixes are cacheable,
    mid-edge divergence splits at the page boundary, and the tree holds one
    reference per owned page (so eviction is the only way pages die)."""
    page = 4
    pool = PagePool(16)
    radix = RadixCache(pool, page)
    toks = np.arange(12, dtype=np.int32)
    pages = [pool.alloc() for _ in range(3)]
    radix.insert(toks, pages)
    assert radix.held_pages == 3
    assert all(pool.page_refs(p) == 2 for p in pages)  # caller + tree
    for p in pages:
        pool.release(p)

    # exact and partial matches, at page granularity
    m, mp = radix.match(toks, len(toks))
    assert m == 12 and [int(x) for x in mp] == pages
    div = np.concatenate([toks[:6], np.array([99, 98], np.int32)])
    m2, mp2 = radix.match(div, len(div))
    assert m2 == 6  # mid-page divergence inside page 1: alias pages 0 and 1
    assert [int(x) for x in mp2] == pages[:2]
    # sub-page matches come back raw; the engine's admission path discards
    # them (m >= page required) since CoW would copy the tile anyway
    m3, mp3 = radix.match(np.array([0, 1, 99], np.int32), 3)
    assert m3 == 2 and len(mp3) == 1

    # inserting the divergent branch splits the shared edge page-aligned
    dp = pool.alloc()
    radix.insert(div, [pages[0], dp])
    assert radix.held_pages == 4  # pages 0,1,2 + the divergent page
    m4, mp4 = radix.match(div, len(div))
    assert m4 == 8 and [int(x) for x in mp4] == [pages[0], dp]
    m5, mp5 = radix.match(toks, len(toks))
    assert m5 == 12 and [int(x) for x in mp5] == pages
    pool.release(dp)
    radix.clear()
    assert pool.in_use == 0


def test_radix_evict_lru_only_unreferenced():
    """Eviction walks childless leaves in LRU order and only frees pages no
    request still reads (refs == 1, i.e. tree-only)."""
    page = 2
    pool = PagePool(8)
    radix = RadixCache(pool, page)
    a = np.array([0, 1, 2, 3], np.int32)
    b = np.array([9, 8, 7, 6], np.int32)
    pa = [pool.alloc(), pool.alloc()]
    pb = [pool.alloc(), pool.alloc()]
    radix.insert(a, pa)  # older (lower LRU clock)
    radix.insert(b, pb)
    # drop caller refs on a (tree-only); KEEP them on b — a live request
    # still aliases b's pages, so b must survive eviction
    for p in pa:
        pool.release(p)
    freed = radix.evict(2)
    assert freed == 2  # branch a (LRU, unreferenced) went; b survived
    assert radix.match(b, 4)[0] == 4 and radix.match(a, 4)[0] == 0
    for p in pb:
        pool.release(p)
    radix.clear()
    assert pool.in_use == 0


# --------------------------------------------------------------------------
# gather_pages with aliased tables
# --------------------------------------------------------------------------


def test_gather_pages_aliased_tables_parity():
    """Two rows whose page tables alias the same physical prefix page must
    gather bit-identical prefix rows, equal to a private-copy layout — the
    read side needs no CoW awareness."""
    import jax.numpy as jnp

    from repro.models.layers import gather_pages

    page, n_pages, KV, hd = 4, 6, 2, 3
    rng = np.random.default_rng(0)
    # a one-layer stack in the stored layout (L, KV, rows, hd)
    pool = jnp.asarray(
        rng.normal(size=(1, KV, n_pages * page, hd)).astype(np.float32)
    )
    # rows 0 and 1 share physical page 2 for vtile 0; diverge on vtile 1
    aliased = jnp.asarray(np.array([[2, 0], [2, 1]], np.int32))
    private = jnp.asarray(np.array([[2, 0], [3, 1]], np.int32))
    # make the "private copy" page 3 hold the same values as shared page 2
    pool_priv = pool.at[:, :, 3 * page:4 * page].set(pool[:, :, 2 * page:3 * page])
    out_a = np.asarray(gather_pages(pool, aliased, 2 * page, page))
    out_p = np.asarray(gather_pages(pool_priv, private, 2 * page, page))
    # shared vtile 0 rows identical across the two rows of the aliased table
    np.testing.assert_array_equal(out_a[0][:page], out_a[1][:page])
    # and aliasing == private copy, bit for bit
    np.testing.assert_array_equal(out_a, out_p)


# --------------------------------------------------------------------------
# Engine: shared-prefix vs cold-start token identity (the parity matrix)
# --------------------------------------------------------------------------

# pattern, pattern_arg, impl, scheduling mode
PREFIX_CASES = [
    ("dense", None, "xla_chunked", "admission"),
    ("dense", None, "flash_kernel", "admission"),
    ("dense", None, "xla_chunked", "chunked"),
    ("dense", None, "flash_kernel", "chunked"),
    ("window", 16, "xla_chunked", "admission"),
    ("window", 16, "flash_kernel", "chunked"),
    ("butterfly", None, "xla_chunked", "chunked"),
    ("butterfly", None, "flash_kernel", "admission"),
]


@pytest.mark.parametrize("pattern,arg,impl,mode", PREFIX_CASES)
def test_shared_prefix_matches_cold_start(pattern, arg, impl, mode):
    """With the radix cache on, requests sharing a long prefix must emit
    EXACTLY the tokens the cold-start (prefix_cache=False) engine emits —
    for every pattern and backend, both scheduler modes, GQA included
    (reduced qwen3 is 4 query heads over 2 kv heads).  Sharing must actually
    engage (prefix_hit_tokens > 0) and fewer prompt tokens must be prefilled
    than the cold run; the pool drains either way."""
    cfg = _cfg(pattern, arg, impl)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    mesh = make_local_mesh()
    chunked = mode == "chunked"
    kw = dict(batch=2, cache_len=512, chunked=chunked, chunk_size=32,
              paged=True)

    cold = ServeLoop(cfg, mesh, params, prefix_cache=False, **kw)
    ref = cold.run(_shared_reqs(cfg))
    warm = ServeLoop(cfg, mesh, params, **kw)
    out = warm.run(_shared_reqs(cfg))

    for r1, r2 in zip(ref, out):
        assert r2.generated == r1.generated, f"uid {r1.uid}"
    assert cold.stats["prefix_hit_tokens"] == 0
    assert warm.stats["prefix_hit_tokens"] > 0
    assert warm.stats["prefill_tokens"] < cold.stats["prefill_tokens"]
    warm.close()  # drops the persistent radix refs; raises on leaks
    cold.close()
    assert warm.pool.in_use == 0 and cold.pool.in_use == 0


def test_cow_sibling_divergence_isolation():
    """Mid-page divergence: the donor caches 2 full pages (260 tokens), the
    sibling shares only 200 — its first suffix write lands inside the shared
    frontier page and MUST fork a private copy (cow_forks >= 1) while both
    requests' tokens stay identical to the cold engine (the donor's view of
    the shared page is never corrupted)."""
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    mesh = make_local_mesh()
    rng = np.random.default_rng(3)
    shared = rng.integers(0, cfg.vocab, size=200).astype(np.int32)
    donor = np.concatenate([shared, rng.integers(0, cfg.vocab, size=60).astype(np.int32)])
    sib = np.concatenate([shared, rng.integers(0, cfg.vocab, size=30).astype(np.int32)])

    def mk():
        return [Request(uid=0, prompt=donor, max_new=4),
                Request(uid=1, prompt=sib, max_new=4)]

    for chunked in (False, True):
        kw = dict(batch=1, cache_len=512, chunked=chunked, chunk_size=32)
        ref = ServeLoop(cfg, mesh, params, **kw).run(mk())
        loop = ServeLoop(cfg, mesh, params, paged=True, **kw)
        out = loop.run(mk())
        for r1, r2 in zip(ref, out):
            assert r2.generated == r1.generated, (chunked, r1.uid)
        assert loop.stats["cow_forks"] >= 1, chunked
        assert loop.stats["prefix_hit_tokens"] == 200, chunked
        loop.close()
        assert loop.pool.in_use == 0


def test_eviction_then_readmit_correct():
    """Pool pressure must evict cached prefixes (LRU) instead of
    backpressuring forever, and a LATER request re-using an evicted prefix
    simply re-prefills cold — same tokens, pool drained."""
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    mesh = make_local_mesh()
    rng = np.random.default_rng(7)
    pa = rng.integers(0, cfg.vocab, size=300).astype(np.int32)
    pb = rng.integers(0, cfg.vocab, size=300).astype(np.int32)
    # a, b, a again: caching a (3 pages) then b forces a's eviction in a
    # 4-page pool; the third request re-admits the evicted prefix
    def mk():
        return [Request(uid=0, prompt=pa, max_new=3),
                Request(uid=1, prompt=pb, max_new=3),
                Request(uid=2, prompt=pa.copy(), max_new=3)]

    kw = dict(batch=1, cache_len=512, chunked=True, chunk_size=32)
    ref = ServeLoop(cfg, mesh, params, **kw).run(mk())
    loop = ServeLoop(cfg, mesh, params, paged=True, pool_pages=4, **kw)
    out = loop.run(mk())
    for r1, r2 in zip(ref, out):
        assert r2.generated == r1.generated, f"uid {r1.uid}"
    assert loop.stats["prefix_evicted_pages"] > 0
    loop.close()
    assert loop.pool.in_use == 0


def test_prefix_cache_off_is_pr5_behaviour():
    """prefix_cache=False must reproduce the PR 5 engine exactly: no radix
    stats movement, prefill_tokens == sum of prompt lengths."""
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    reqs = _shared_reqs(cfg)
    total = sum(len(r.prompt) for r in reqs)
    loop = ServeLoop(cfg, make_local_mesh(), params, batch=2, cache_len=512,
                     chunked=True, chunk_size=32, paged=True,
                     prefix_cache=False)
    loop.run(reqs)
    assert loop.stats["prefix_hits"] == 0
    assert loop.stats["prefill_tokens"] == total
    loop.close()
    assert loop.pool.in_use == 0
