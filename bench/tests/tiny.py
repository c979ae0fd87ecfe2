"""A tiny cell for the CPU tests: the qwen3 configuration file at toy
widths, a short mix, and a checkout-like directory holding them; or, with
:func:`use_dense_gqa`, a second model family added as files."""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)

TINY = {"num_hidden_layers": 2, "hidden_size": 64, "vocab_size": 512,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "intermediate_size": 96}

MIX = {"prompt_len": [260, 600], "output_len": [3, 6], "round_requests": 3,
       "serving": {"batch": 2, "cache_len": 1024, "chunk": 128, "pool_pages": 16},
       "check_requests": 2}


def make_root(tmp: str, config: str = "qwen3-0.6b-bfly", limit: float = 1.0) -> str:
    """A directory laid out like a checkout, with one cell ``tiny.mix`` on
    the toy-width copy of ``config``; the CPU gets a row of made-up peaks."""
    root = os.path.join(tmp, "root")
    os.makedirs(os.path.join(root, "bench", "configs"))
    os.makedirs(os.path.join(root, "bench", "traffic"))
    os.makedirs(os.path.join(root, "bench", "limits"))
    for d in ("metrics", "models"):
        shutil.copytree(os.path.join(BENCH, d), os.path.join(root, "bench", d),
                        ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(BENCH, "configs", f"{config}.json")) as f:
        cfg = json.load(f)
    cfg.update(TINY)
    _dump(cfg, root, "bench/configs/tiny.json")
    _dump(MIX, root, "bench/traffic/mix.json")
    _dump({"widest_logit_gap": {"limit": limit}}, root, "bench/limits/tiny.mix.json")
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    peaks["cpu"] = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                    "hbm_bytes": 1e10, "source": "made up, tests only"}
    _dump(peaks, root, "bench/peaks.json")
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "tiny", "source": "tests", "file": "bench/configs/tiny.json",
                         "reduced": [], "why": "tests"}]
    bench["workloads"] = [{"name": "tiny.mix", "config": "tiny", "traffic": "mix",
                           "chips": 1, "why": "tests"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    _dump(bench, root, "BENCHMARK.json")
    return root


def use_dense_gqa(root: str) -> str:
    """Turn ``root``'s tiny cell into one of the test-only dense GQA family
    (``dense_gqa.py``, the program's ``yi-6b`` entry): its model code and
    its configuration file, and nothing else, are added to the root."""
    shutil.copy(os.path.join(HERE, "dense_gqa.py"),
                os.path.join(root, "bench", "models", "dense_gqa.py"))
    path = os.path.join(root, "bench", "configs", "tiny.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg.update(name="dense-gqa-tiny", model_code="dense_gqa", rope_theta=5000000)
    cfg["serving"] = dict(cfg["serving"], registry="yi-6b+flash+butterfly_attn")
    _dump(cfg, root, "bench/configs/tiny.json")
    return root


def _dump(obj, root, rel):
    with open(os.path.join(root, rel), "w") as f:
        json.dump(obj, f, indent=1)
