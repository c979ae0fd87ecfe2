"""Mod-window ring page-table translation properties.

A sliding-window request's absolute virtual tile ``j`` lives in page-table
slot ``j % ring_tiles``.  These tests drive arbitrary serve interleavings —
a prompt streamed in chunks, then single-token decode steps, positions
running laps around the ring — through the REAL table builders
(:func:`ring_chunk_tables` / :func:`ring_decode_tables` /
:func:`translate_tables`) against a masked-oracle simulator, checking at
every step:

* phase alignment — every live tile translates to physical slot
  ``tile % ring_tiles``, whatever the interleaving;
* token identity — the ring exposes EXACTLY the window's positions and none
  of them has been overwritten by a later lap (``ring_tiles_for``'s
  ``R * page >= window + page`` slack is what makes this hold at the
  partially-overwritten frontier slot);
* the slot-ordered XLA gather (``ring_kpos``) reproduces masked full-cache
  attention bit-for-bit, GQA included.

The property-based layer runs only where hypothesis is installed; a
deterministic seeded sweep of the same invariant always runs.
"""

import numpy as np
import pytest

from repro.core import sparsity


# --------------------------------------------------------------------------
# The invariant, checked against a masked-oracle ring simulator
# --------------------------------------------------------------------------


def _check_interleaving(window, page, plen, chunk, steps):
    """Simulate one request's life: prompt of ``plen`` tokens admitted in
    ``chunk``-sized pieces, then ``steps`` decode steps.  The simulator
    writes each position's id into its mod-window ring row; the tables must
    expose exactly the oracle window at every step."""
    R = sparsity.ring_tiles_for(window, max(chunk, 1), page)
    # the collision-freedom slack: one step's live span always fits
    assert R * page >= window + page

    ring = np.full((R, page), -1, np.int64)  # absolute position per row
    pt = np.arange(R, dtype=np.int32)[None, :]  # identity table, B=1

    def write(p):
        ring[(p // page) % R, p % page] = p

    def check(kv, live, queries):
        phys, virt, live2 = (
            np.asarray(x)
            for x in sparsity.translate_tables(
                np.asarray(kv), np.asarray(live), pt, R, ring_tiles=R
            )
        )
        tiles = {}
        for t, lv, ph in zip(virt[0], live2[0], phys[0]):
            if lv:
                assert ph == t % R, f"tile {t} in slot {ph} != {t % R}"
                tiles[int(t)] = int(ph)
        for q in queries:
            for p in range(max(0, q - window + 1), q + 1):
                assert p // page in tiles, (
                    f"q={q}: window position {p} not covered "
                    f"(tiles {sorted(tiles)})"
                )
                got = ring[(p // page) % R, p % page]
                assert got == p, (
                    f"q={q}: position {p} lapped — ring row holds {got}"
                )

    pos = 0
    while pos < plen:  # chunked prefill: write the chunk, then attend
        n = min(chunk, plen - pos)
        for p in range(pos, pos + n):
            write(p)
        kv, live = sparsity.ring_chunk_tables([pos], [n], chunk, window, page, R)
        check(kv, live, range(pos, pos + n))
        pos += n
    for _ in range(steps):  # decode: one write + one query per step
        write(pos)
        pos += 1
        kv, live = sparsity.ring_decode_tables([pos], window, page, R)
        check(kv, live, [pos - 1])


# hand-picked corners: window == page, window < page, multi-lap decode,
# chunk larger than window, single-token everything
SWEEP = [
    (4, 4, 9, 4, 14),
    (3, 8, 5, 2, 20),
    (10, 4, 7, 8, 25),
    (10, 4, 1, 1, 30),
    (17, 8, 40, 16, 12),
    (5, 2, 23, 3, 19),
    (1, 1, 3, 1, 9),
    (7, 4, 12, 5, 0),
]


@pytest.mark.parametrize("window,page,plen,chunk,steps", SWEEP)
def test_ring_translation_sweep(window, page, plen, chunk, steps):
    _check_interleaving(window, page, plen, chunk, steps)


def test_ring_translation_random_interleavings():
    """Seeded random sweep — the always-on stand-in for the property test on
    boxes without hypothesis."""
    rng = np.random.default_rng(11)
    for _ in range(20):
        window = int(rng.integers(1, 24))
        page = int(rng.integers(1, 9))
        plen = int(rng.integers(1, 50))
        chunk = int(rng.integers(1, 12))
        steps = int(rng.integers(0, 3 * window + 3))  # several laps
        _check_interleaving(window, page, plen, chunk, steps)


def test_ring_property_hypothesis():
    """Property form: ANY (window, page, plen, chunk, steps) interleaving
    keeps phase alignment and masked-oracle token identity."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.settings(max_examples=40, deadline=None)
    @hyp.given(
        window=st.integers(1, 30),
        page=st.integers(1, 8),
        plen=st.integers(1, 60),
        chunk=st.integers(1, 10),
        steps=st.integers(0, 40),
    )
    def prop(window, page, plen, chunk, steps):
        _check_interleaving(window, page, plen, chunk, steps)

    prop()


# --------------------------------------------------------------------------
# Slot-ordered gather == masked full-cache attention (GQA)
# --------------------------------------------------------------------------


def test_ring_gather_decode_matches_masked_oracle_gqa():
    """The XLA ring branch's building blocks — ``gather_pages`` over a
    mod-window table + ``ring_kpos`` absolute positions — reproduce masked
    full-cache decode attention exactly, with 4 query heads over 2 kv heads
    and the frontier deep into the third lap."""
    import jax
    import jax.numpy as jnp

    from repro.models import layers

    window, page = 10, 4
    R = sparsity.ring_tiles_for(window, 1, page)
    L = 37  # cur_len: several laps past R * page rows
    H, KV, hd = 4, 2, 8

    kf, vf, kq = jax.random.split(jax.random.PRNGKey(0), 3)
    k_full = jax.random.normal(kf, (1, L, KV, hd), jnp.float32)
    v_full = jax.random.normal(vf, (1, L, KV, hd), jnp.float32)
    q = jax.random.normal(kq, (1, H, hd), jnp.float32)

    # write the last window's rows ringwise, as the engine's scatter does
    pool_k = np.zeros((R * page, KV, hd), np.float32)
    pool_v = np.zeros((R * page, KV, hd), np.float32)
    for p in range(L):
        r = ((p // page) % R) * page + p % page
        pool_k[r] = np.asarray(k_full[0, p])
        pool_v[r] = np.asarray(v_full[0, p])

    pt = jnp.arange(R, dtype=jnp.int32)[None, :]
    # the stored layout, a one-layer stack (L, KV, rows, hd)
    pool_k = pool_k.transpose(1, 0, 2)[None]
    pool_v = pool_v.transpose(1, 0, 2)[None]
    kg = layers.gather_pages(jnp.asarray(pool_k), pt, R * page, page)
    vg = layers.gather_pages(jnp.asarray(pool_v), pt, R * page, page)
    kpos = layers.ring_kpos(jnp.asarray([L - 1]), page, R)
    mask = (kpos < L) & (kpos > L - 1 - window)
    out = layers.decode_attention(q, kg, vg, None, pattern_mask=mask)

    opos = jnp.arange(L, dtype=jnp.int32)[None, :]
    omask = (opos < L) & (opos > L - 1 - window)
    ref = layers.decode_attention(q, k_full, v_full, None, pattern_mask=omask)

    assert float(jnp.max(jnp.abs(out - ref))) < 1e-6
