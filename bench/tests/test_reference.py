"""The reference's tile-gathered attention against a dense softmax under the
token-level butterfly mask built from the definition."""

import numpy as np
import pytest

import reference


@pytest.mark.parametrize("pattern", ["butterfly", "dense"])
def test_attention_matches_masked_softmax(pattern):
    tile, n, h, kvh, hd = 8, 96, 4, 2, 16
    rng = np.random.default_rng(1)
    q = rng.normal(size=(n, h, hd)).astype(np.float32)
    k = rng.normal(size=(n, kvh, hd)).astype(np.float32)
    v = rng.normal(size=(n, kvh, hd)).astype(np.float32)
    table = reference.butterfly_tiles(n // tile, pattern)
    got = np.asarray(reference.attention(q, k, v, table, tile, False))

    pos = np.arange(n)
    m = pos[None, :] <= pos[:, None]
    if pattern == "butterfly":
        x = (pos[:, None] // tile) ^ (pos[None, :] // tile)
        m &= (x & (x - 1)) == 0
    g = h // kvh
    want = np.zeros_like(q)
    for head in range(h):
        s = q[:, head] @ k[:, head // g].T / np.sqrt(hd)
        s = np.where(m, s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        want[:, head] = p @ v[:, head // g]
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_butterfly_tiles_definition():
    t = reference.butterfly_tiles(9, "butterfly")
    assert sorted(x for x in t[6] if x >= 0) == [2, 4, 6]
    assert sorted(x for x in t[8] if x >= 0) == [0, 8]
    assert sorted(x for x in t[7] if x >= 0) == [3, 5, 6, 7]
