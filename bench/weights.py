"""Weights made by the benchmark from ``--seed``, in the layout the program
serves.

One jitted call on the device makes every leaf from the seed, in the
program's parameter dtype (float32 masters).  The tree is built from the
configuration file's sizes alone; :func:`check_layout` compares it with
the program's own abstract tree, so a layout change in the program fails
set-up instead of serving something else.  The plain reference reads the
same arrays as data.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from counts import ModelShape, bpmm_plan, linear_sites

__all__ = ["seed_key", "layout", "make_params", "check_layout"]


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


def make_params(ms: ModelShape, seed: int, dtype=jnp.float32):
    """Every leaf from the seed, in one jitted call on the default device."""
    spec = layout(ms)
    leaves, treedef = jax.tree.flatten(spec, is_leaf=_is_leaf)

    @jax.jit
    def build(key):
        out = []
        for i, (shape, scale) in enumerate(leaves):
            if scale == 0.0:
                out.append(jnp.zeros(shape, dtype))
            else:
                k = jax.random.fold_in(key, i)
                out.append(jax.random.normal(k, shape, dtype) * scale)
        return jax.tree.unflatten(treedef, out)

    return build(seed_key(seed))


def check_layout(params, abstract) -> None:
    """Raise unless ``params`` has the program's tree, shapes and dtypes."""
    def table(tree):
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        return {jax.tree_util.keystr(p): (tuple(a.shape), str(a.dtype))
                for p, a in flat}

    mine, theirs = table(params), table(abstract)
    if mine != theirs:
        diff = sorted(set(mine.items()) ^ set(theirs.items()))
        raise RuntimeError(
            "benchmark weights do not match the program's parameter layout: "
            f"{diff[:6]}")
