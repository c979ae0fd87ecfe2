"""Model code of a second family, for the tests: a llama-style dense GQA
decoder (no q/k norms, SwiGLU MLP, dense linears), served by the
program's ``yi-6b`` registry entry.  A test copies it into its root's
``bench/models/`` beside the qwen3 module, as a configuration of a new
family would be added.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import counts
import reference
import weights

__all__ = ["shape", "program_config", "make_params", "logits_at"]

FIELDS = {  # configuration file key -> program ModelConfig field
    "num_hidden_layers": ("n_layers", int), "hidden_size": ("d_model", int),
    "vocab_size": ("vocab", int), "num_attention_heads": ("n_heads", int),
    "num_key_value_heads": ("n_kv_heads", int), "head_dim": ("head_dim", int),
    "intermediate_size": ("d_ff", int), "rope_theta": ("rope_theta", float),
    "rms_norm_eps": ("norm_eps", float),
}


def shape(config: dict) -> counts.ModelShape:
    return counts.ModelShape.from_config(config)


def program_config(config: dict):
    from repro.configs import registry

    s = config["serving"]
    mc = dataclasses.replace(registry.get(s["registry"]),
                             **{f: cast(config[k]) for k, (f, cast) in FIELDS.items()})
    spec = mc.attention_spec
    if mc.qk_norm or (spec.impl, spec.pattern) != (s["attn_impl"], s["attn_pattern"]):
        raise ValueError(f"{s['registry']} is not the model the file states")
    return mc


def make_params(ms: counts.ModelShape, seed: int):
    n, d, hd = ms.layers, ms.d_model, ms.head_dim

    def lin(din, dout):
        return {"w": ((n, din, dout), 1.0 / math.sqrt(din))}

    return weights.build({
        "embed": ((ms.vocab, d), 1.0),
        "head": ((d, ms.vocab), 1.0 / math.sqrt(d)),
        "final_norm": {"w": ((1, d), 0.0)},
        "layers": {"slot00": {
            "mixer_norm": {"w": ((n, d), 0.0)},
            "attn": {"wq": lin(d, ms.heads * hd), "wk": lin(d, ms.kv_heads * hd),
                     "wv": lin(d, ms.kv_heads * hd), "wo": lin(ms.heads * hd, d)},
            "ffn_norm": {"w": ((n, d), 0.0)},
            "ffn": {"w1": lin(d, ms.d_ff), "w3": lin(d, ms.d_ff), "w2": lin(ms.d_ff, d)},
        }},
    }, seed)


@functools.partial(jax.jit, static_argnames=("sizes", "control"))
def _forward(params, tokens, read, *, sizes, control):
    heads, kvh, hd, eps, theta, tile, pattern = sizes
    t = tokens.shape[0]
    table = reference.butterfly_tiles(t // tile, pattern)
    pos = jnp.arange(t)

    def layer(x, p):
        a, f = p["attn"], p["ffn"]
        h = reference.rms(x, p["mixer_norm"]["w"], eps)
        q = reference.mm(h, a["wq"]["w"], control).reshape(t, heads, hd)
        k = reference.mm(h, a["wk"]["w"], control).reshape(t, kvh, hd)
        v = reference.mm(h, a["wv"]["w"], control).reshape(t, kvh, hd)
        q, k = reference.rope(q, pos, theta), reference.rope(k, pos, theta)
        o = reference.attention(q, k, v, table, tile, control).reshape(t, heads * hd)
        x = x + reference.mm(o, a["wo"]["w"], control)
        h = reference.rms(x, p["ffn_norm"]["w"], eps)
        u = (jax.nn.silu(reference.mm(h, f["w1"]["w"], control))
             * reference.mm(h, f["w3"]["w"], control))
        return x + reference.mm(u, f["w2"]["w"], control), None

    x, _ = jax.lax.scan(layer, params["embed"][tokens], params["layers"]["slot00"])
    x = reference.rms(x[read], params["final_norm"]["w"][0], eps)
    return reference.mm(x, params["head"], control)


def logits_at(params, config: dict, tokens, read, control: bool = False):
    s = config["serving"]
    sizes = (config["num_attention_heads"], config["num_key_value_heads"],
             config["head_dim"], float(config["rms_norm_eps"]),
             float(config["rope_theta"]), s["tile"], s["attn_pattern"])
    tok, rd = reference.pad(tokens, read, s["tile"])
    out = _forward(params, tok, rd, sizes=sizes, control=control)
    return np.asarray(out, np.float32)[: len(read)]
