"""The count functions against brute force over the token-level mask and
the dense form of the Monarch factors."""

import numpy as np
import pytest

import counts
import reference


def _mask(n: int, tile: int, pattern: str) -> np.ndarray:
    """Token-level mask from the pattern's definition."""
    q = np.arange(n)[:, None]
    k = np.arange(n)[None, :]
    m = k <= q
    if pattern == "butterfly":
        x = (q // tile) ^ (k // tile)
        m &= (x & (x - 1)) == 0
    return m


def _shape(pattern: str, tile: int, linears: str = "dense") -> counts.ModelShape:
    return counts.ModelShape(layers=3, d_model=64, heads=4, kv_heads=2,
                             head_dim=16, d_ff=96, vocab=100, pattern=pattern,
                             tile=tile, linears=linears)


@pytest.mark.parametrize("pattern", ["butterfly", "dense"])
@pytest.mark.parametrize("tile", [4, 8])
def test_live_keys_and_rows_match_mask(pattern, tile):
    n = 23 * tile + 5
    m = _mask(n, tile, pattern)
    for p in range(n):
        assert counts.live_keys(p, tile, pattern) == m[p].sum()
    for s in range(0, n, 3 * tile + 1):
        for e in (s + 1, min(s + 2 * tile + 3, n), min(s + 5 * tile, n)):
            rows = m[s:e].any(axis=0).sum()
            assert counts.chunk_live_rows(s, e, tile, pattern) == rows


def test_request_attention_sums_the_mask():
    ms = _shape("butterfly", 8)
    prompt, new, chunk = 150, 9, 32
    calls, (df, db) = counts.request_attention(ms, prompt, new, chunk)
    m = _mask(prompt + new, 8, "butterfly")
    per_pair = 4.0 * ms.head_dim * ms.heads * ms.layers
    assert sum(f for f, _ in calls) == per_pair * m[:prompt].sum()
    assert df == per_pair * m[prompt:prompt + new - 1].sum()
    assert len(calls) == -(-prompt // chunk)
    kv_row = 2.0 * ms.kv_heads * ms.head_dim * 2 * ms.layers
    qo = 2.0 * ms.heads * ms.head_dim * 2 * ms.layers
    rows0 = m[:chunk].any(axis=0).sum()
    assert calls[0][1] == kv_row * rows0 + qo * chunk
    assert db == kv_row * m[prompt:prompt + new - 1].sum() + qo * (new - 1)


@pytest.mark.parametrize("din,dout", [(64, 128), (128, 64), (64, 96), (96, 64)])
def test_monarch_dense_matches_the_factored_apply(din, dout):
    pl = counts.bpmm_plan(din, dout)
    rng = np.random.default_rng(0)
    r = rng.normal(size=(pl["gout"], pl["gin"], pl["nb"], pl["b"], pl["b"]))
    l = rng.normal(size=(pl["gout"], pl["gin"], pl["b"], pl["nb"], pl["nb"]))
    w = np.asarray(reference.monarch_dense(r.astype(np.float32),
                                           l.astype(np.float32), din, dout))
    x = rng.normal(size=(din,))
    xg = np.zeros(pl["gin"] * pl["piece"])
    xg[:din] = x
    xg = xg.reshape(pl["gin"], pl["nb"], pl["b"])
    u = np.einsum("oghij,ghj->oghi", r, xg)
    y = np.einsum("ogjhk,ogkj->oghj", l, u).sum(axis=1).reshape(-1)[:dout]
    np.testing.assert_allclose(x @ w, y, rtol=1e-4, atol=1e-4)


def test_bpmm_counts_are_the_factor_sizes():
    ms = _shape("butterfly", 8, linears="bpmm")
    sizes = 0
    io = 0
    for _, din, dout in counts.linear_sites(ms):
        pl = counts.bpmm_plan(din, dout)
        assert pl["piece"] == 1 << int(np.floor(np.log2(min(din, dout))))
        assert pl["b"] * pl["nb"] == pl["piece"] and max(pl["b"], pl["nb"]) <= 512
        sizes += pl["gout"] * pl["gin"] * (pl["nb"] * pl["b"] ** 2 + pl["b"] * pl["nb"] ** 2)
        io += (pl["gin"] + pl["gout"]) * pl["piece"]
    assert counts.linear_flops_per_token(ms) == 2.0 * sizes
    assert counts.bpmm_weight_bytes(ms) == 2.0 * sizes
    assert counts.bpmm_io_bytes_per_token(ms) == 2.0 * io


def test_model_flops_adds_up():
    ms = _shape("butterfly", 8)
    prompt, new, chunk = 40, 5, 16
    calls, (df, _) = counts.request_attention(ms, prompt, new, chunk)
    dense_lin = 2.0 * (64 * 64 + 2 * 64 * 32 + 64 * 64 + 3 * 64 * 96)
    want = ((prompt + new - 1) * ms.layers * dense_lin
            + sum(f for f, _ in calls) + df + 2.0 * 64 * 100 * new)
    assert counts.linear_flops_per_token(ms) == dense_lin
    assert counts.model_flops(ms, prompt, new, chunk) == pytest.approx(want)
