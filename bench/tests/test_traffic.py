"""Every mix file: the same seed gives the same rounds; every seed serves
the same sizes in the same order, one length from each stratum."""

import os

import numpy as np
import pytest

import traffic

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(traffic.HERE, "traffic"))
               if f.endswith(".json"))
VOCAB = 151936


def _sizes(rnd):
    return [(len(x), m) for x, m in rnd]


@pytest.mark.parametrize("name", MIXES)
def test_rounds_repeat_by_seed_and_keep_their_sizes(name):
    mix = traffic.load(name)
    a = traffic.make_round(mix, 2**31 + 17, 3, VOCAB)
    b = traffic.make_round(mix, 2**31 + 17, 3, VOCAB)
    c = traffic.make_round(mix, 5, 3, VOCAB)
    assert [(x.tolist(), m) for x, m in a] == [(x.tolist(), m) for x, m in b]
    assert any(not np.array_equal(x, y) for (x, _), (y, _) in zip(a, c))
    assert _sizes(a) == _sizes(c) == traffic.round_sizes(mix, 3)
    other = traffic.make_round(mix, 2**31 + 17, 4, VOCAB)
    assert sorted(_sizes(other)) == sorted(_sizes(a))
    n = mix["round_requests"]
    assert len(a) == n
    for key, got in (("prompt_len", [p for p, _ in _sizes(a)]),
                     ("output_len", [m for _, m in _sizes(a)])):
        lo, hi = mix[key]
        u = np.log(np.asarray(got) / lo) / np.log(hi / lo)
        # one length in each stratum, up to rounding at the edges
        strata = np.sort(np.clip(np.floor(u * n + 1e-9), 0, n - 1))
        assert np.abs(strata - np.arange(n)).max() <= 1
        assert all(lo <= x <= hi for x in got)
    assert all(x.dtype == np.int32 and 0 <= x.min() and x.max() < VOCAB for x, _ in a)
    assert traffic.longest(mix) == max(p + m - 1 for p, m in _sizes(a))
    assert traffic.longest(mix) <= mix["serving"]["cache_len"]
