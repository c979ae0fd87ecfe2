"""Qwen3's model code for the benchmark: dense GQA with per-head q/k
RMSNorm and a SwiGLU MLP, with dense or Monarch BPMM linears
(``bench/configs/qwen3-0.6b-*.json``).

The four functions the harness calls, over the sizes in ``counts``, the
weights in ``weights`` and the float32 forward pass in ``reference``.
"""

from __future__ import annotations

import dataclasses

import counts
import reference
import weights

__all__ = ["shape", "program_config", "make_params", "logits_at"]

HF_FIELDS = {  # configuration file key -> program ModelConfig field
    "num_hidden_layers": ("n_layers", int),
    "hidden_size": ("d_model", int),
    "vocab_size": ("vocab", int),
    "num_attention_heads": ("n_heads", int),
    "num_key_value_heads": ("n_kv_heads", int),
    "head_dim": ("head_dim", int),
    "intermediate_size": ("d_ff", int),
    "rope_theta": ("rope_theta", float),
    "rms_norm_eps": ("norm_eps", float),
}


def shape(config: dict) -> counts.ModelShape:
    return counts.ModelShape.from_config(config)


def program_config(config: dict):
    """The program's ModelConfig: the registry entry the file names, with
    every size taken from the file."""
    from repro.configs import registry

    s = config["serving"]
    mc = registry.get(s["registry"])
    mc = dataclasses.replace(mc, **{
        field: cast(config[key]) for key, (field, cast) in HF_FIELDS.items()})
    spec = mc.attention_spec
    if (spec.impl, spec.pattern) != (s["attn_impl"], s["attn_pattern"]):
        raise ValueError(f"{s['registry']} runs {spec.impl}/{spec.pattern}, "
                         f"the file states {s['attn_impl']}/{s['attn_pattern']}")
    if (mc.dtype, mc.param_dtype) != (s["compute_dtype"], s["param_dtype"]):
        raise ValueError(f"{s['registry']} computes in {mc.dtype} over "
                         f"{mc.param_dtype}, the file states otherwise")
    linears = "dense" if mc.butterfly.impl == "dense" else "bpmm"
    if linears != s["linears"]:
        raise ValueError(f"{s['registry']} has {linears} linears")
    return mc


def make_params(ms: counts.ModelShape, seed: int):
    return weights.make_params(ms, seed)


def logits_at(params, config: dict, tokens, read, control: bool = False):
    return reference.logits_at(params, config, tokens, read, control=control)
