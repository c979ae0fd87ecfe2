"""The one traffic generator: reads a mix file under ``bench/traffic/``.

A mix is served in back-to-back rounds of ``round_requests`` requests, all
submitted at the round's start.  Prompt and output lengths are stratified
log-uniform over the mix's ranges: the midpoint of each of
``round_requests`` equal-probability strata, paired and ordered by one
fixed permutation per round index.  Every seed serves the same sizes in
the same order and draws only the token ids.  Seed-drawn lengths and
orders were measured and refused: at a round or two per window the order
decides how well the rounds fill the batch, and it moved a run's
``output_tok_s`` by 16% from seed to seed, against 0.4% between two runs
of one seed.

Keys of a mix file:

* ``source``: the public trace the ranges are fitted to;
* ``prompt_len``, ``output_len``: [lo, hi] token ranges, log-uniform;
* ``round_requests``: requests per round;
* ``serving``: the loop's batch, cache length, chunk and page pool, and
  optionally ``loop``, further keyword arguments of ``ServeLoop``;
* ``check_requests``: how many of the window's requests the reference checks.
"""

from __future__ import annotations

import json
import os

import numpy as np

__all__ = ["load", "round_sizes", "make_round", "longest"]

HERE = os.path.dirname(os.path.abspath(__file__))
ORDER_SEED = 0  # the fixed pairing and order of every mix


def load(name: str, root: str = HERE) -> dict:
    with open(os.path.join(root, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    for key in ("prompt_len", "output_len", "round_requests", "serving",
                "check_requests"):
        if key not in mix:
            raise ValueError(f"traffic {name!r}: missing {key!r}")
    return mix


def round_sizes(mix: dict, index: int = 0) -> list[tuple[int, int]]:
    """(prompt length, new tokens) of each request of round ``index``, in
    submission order: the same for every seed."""
    n = int(mix["round_requests"])
    mid = (np.arange(n) + 0.5) / n
    lo, hi = mix["prompt_len"]
    prompts = np.rint(lo * (hi / lo) ** mid).astype(np.int64)
    lo, hi = mix["output_len"]
    outs = np.rint(lo * (hi / lo) ** mid).astype(np.int64)
    pair = np.random.default_rng(ORDER_SEED).permutation(n)
    order = np.random.default_rng((ORDER_SEED, index)).permutation(n)
    return [(int(prompts[i]), int(outs[pair[i]])) for i in order]


def longest(mix: dict) -> int:
    """Most cache rows one request of the mix writes."""
    return max(p + m - 1 for p, m in round_sizes(mix))


def make_round(mix: dict, seed: int, index: int, vocab: int,
               stream: int = 0) -> list[tuple[np.ndarray, int]]:
    """Round ``index`` of a run with ``seed``: (prompt ids, new tokens) per
    request, in the order they are submitted.  ``stream`` separates the
    warm-up's token ids from the window's."""
    rng = np.random.default_rng((seed, stream, index))
    return [(rng.integers(0, vocab, p, dtype=np.int32), m)
            for p, m in round_sizes(mix, index)]
