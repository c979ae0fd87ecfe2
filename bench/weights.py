"""Weights made by the benchmark from ``--seed``, in the layout the program
serves.

:func:`build` makes every leaf of a tree of (shape, scale) from the seed in
one jitted call on the device, in the program's parameter dtype (float32
masters); a model's code gives the tree.  :func:`layout` is Qwen3's, built
from the configuration file's sizes alone.  Set-up compares the weights
with the program's own abstract tree (``harness.check_layout``), so a
layout change in the program fails set-up instead of serving something
else.  The plain reference reads the same arrays as data.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from counts import ModelShape, bpmm_plan, linear_sites

__all__ = ["seed_key", "layout", "build", "make_params"]


def seed_key(seed: int, salt: int = 0) -> jax.Array:
    """A threefry key from any non-negative integer seed (wider than 32
    bits included)."""
    words = np.random.SeedSequence((int(seed), salt)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def _linear(ms: ModelShape, din: int, dout: int, n: int) -> dict:
    """Shape and init scale of each leaf of one stacked linear site."""
    if ms.linears == "dense":
        return {"w": ((n, din, dout), 1.0 / math.sqrt(din))}
    pl = bpmm_plan(din, dout, ms.max_block, ms.max_piece)
    go, gi, b, nb = pl["gout"], pl["gin"], pl["b"], pl["nb"]
    return {
        "r": ((n, go, gi, nb, b, b), 1.0 / math.sqrt(b)),
        "l": ((n, go, gi, b, nb, nb), 1.0 / math.sqrt(nb) / math.sqrt(gi)),
    }


def layout(ms: ModelShape) -> dict:
    """Tree of (shape, scale); scale 0 means zeros (norm offsets: the
    program's norms multiply by ``1 + w``)."""
    n, d = ms.layers, ms.d_model
    sites = {name: _linear(ms, din, dout, n) for name, din, dout in linear_sites(ms)}
    return {
        "embed": ((ms.vocab, d), 1.0),
        "head": ((d, ms.vocab), 1.0 / math.sqrt(d)),
        "final_norm": {"w": ((1, d), 0.0)},
        "layers": {"slot00": {
            "mixer_norm": {"w": ((n, d), 0.0)},
            "attn": {
                "wq": sites["wq"], "wk": sites["wk"], "wv": sites["wv"],
                "wo": sites["wo"],
                "q_norm": ((n, ms.head_dim), 0.0),
                "k_norm": ((n, ms.head_dim), 0.0),
            },
            "ffn_norm": {"w": ((n, d), 0.0)},
            "ffn": {"w1": sites["w1"], "w2": sites["w2"], "w3": sites["w3"]},
        }},
    }


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def build(spec: dict, seed: int, dtype=jnp.float32):
    """Every leaf of ``spec``, a tree of (shape, scale) with scale 0 for
    zeros, from the seed, in one jitted call on the default device.  Leaf
    ``i`` in flattening order draws from the seed's key folded with ``i``."""
    leaves, treedef = jax.tree.flatten(spec, is_leaf=_is_leaf)

    @jax.jit
    def make(key):
        out = []
        for i, (shape, scale) in enumerate(leaves):
            if scale == 0.0:
                out.append(jnp.zeros(shape, dtype))
            else:
                k = jax.random.fold_in(key, i)
                out.append(jax.random.normal(k, shape, dtype) * scale)
        return jax.tree.unflatten(treedef, out)

    return make(seed_key(seed))


def make_params(ms: ModelShape, seed: int, dtype=jnp.float32):
    """Qwen3's weights from the seed."""
    return build(layout(ms), seed, dtype)
