"""Whole runs of a toy-width cell on the CPU, past the harness's look for a
chip: a sound run is correct, for qwen3 and for a second family whose
model code and configuration were added as files; a cell, a mix and a
metric added as files are found by name; a token altered where the loop
produces it, a pool read one page off, and the float8 control in the
program's place all fail the comparison."""

import json
import os
import time

import jax
import pytest

import calibrate
import harness
import tiny
from repro.core import sparsity
from repro.launch.serving import queueing


@pytest.fixture(autouse=True)
def cpu_chips(monkeypatch):
    """The CPU stands in for the chips the cell asks for."""
    monkeypatch.setattr(harness, "require_chips", lambda n: jax.devices()[:n])


def _run(root, cell="tiny.mix", seed=11, trace=False):
    return harness.run_cell(root, cell, seed, 0.5, trace, time.perf_counter())


def _root(tmp_path, family):
    root = tiny.make_root(str(tmp_path), limit=0.05)
    return tiny.use_dense_gqa(root) if family == "dense_gqa" else root


@pytest.mark.parametrize("family", ["qwen3", "dense_gqa"])
def test_sound_run_is_correct(tmp_path, family):
    root = _root(tmp_path, family)
    res = _run(root, seed=2**31 + 3)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 3
    assert set(res["metrics"]) == {"output_tok_s", "itl_p95_ms", "ttft_p95_ms", "setup_s"}
    assert list(res)[-1] == "checks"
    assert res["checks"]["widest_logit_gap"]["value"] <= 0.05


def test_cell_mix_and_metric_added_as_files(tmp_path):
    root = tiny.make_root(str(tmp_path), config="qwen3-0.6b-bpmm", limit=0.05)
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "traffic", "mix.json")) as f:
        mix = json.load(f)
    mix["prompt_len"], mix["round_requests"] = [140, 300], 4
    with open(os.path.join(b, "traffic", "other.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(b, "configs", "tiny.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(b, "configs", "tiny2.json"), "w") as f:
        json.dump(dict(cfg, num_hidden_layers=1), f)
    with open(os.path.join(b, "metrics", "chunk_calls.py"), "w") as f:
        f.write("def read(run):\n"
                "    return sum(rd.stats['chunk_calls'] for rd in run.rounds)\n")
    with open(os.path.join(b, "limits", "tiny2.other.json"), "w") as f:
        json.dump({"widest_logit_gap": {"limit": 0.05}}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny2", "source": "tests",
                             "file": "bench/configs/tiny2.json", "reduced": [],
                             "why": "tests"})
    bench["workloads"].append({"name": "tiny2.other", "config": "tiny2",
                               "traffic": "other", "chips": 1, "why": "tests"})
    bench["per_layer"].append({"name": "chunk_calls", "unit": "calls",
                               "better": "lower", "source": "program_counter",
                               "layer": "scheduler", "moves": "output_tok_s",
                               "workloads": ["tiny2.other"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    res = _run(root, cell="tiny2.other", trace=True)
    assert res["correct"]
    assert res["metrics"]["chunk_calls"]["value"] >= 4
    assert "decode_occupancy" in res["metrics"]
    assert "output_tok_s" not in res["metrics"]


@pytest.mark.parametrize("family", ["qwen3", "dense_gqa"])
def test_altered_token_is_not_correct(tmp_path, monkeypatch, family):
    root = _root(tmp_path, family)
    push = queueing._AsyncTokens.push
    calls = []

    def altered(self, dev, sinks):
        calls.append(1)
        if len(calls) % 3 == 0:  # every third resolved step's tokens
            dev = (dev + 1) % tiny.TINY["vocab_size"]
        return push(self, dev, sinks)

    monkeypatch.setattr(queueing._AsyncTokens, "push", altered)
    res = _run(root)
    assert not res["correct"]
    assert res["checks"]["widest_logit_gap"]["value"] > 0.05


def test_pool_read_one_page_off_is_not_correct(tmp_path, monkeypatch):
    root = tiny.make_root(str(tmp_path), limit=0.05)
    monkeypatch.setattr(sparsity, "translate_tables", sparsity.translate_tables)
    calibrate.shift_pages()
    res = _run(root)
    assert not res["correct"]
    assert res["checks"]["widest_logit_gap"]["value"] > 0.05


def test_control_fails_where_the_program_passes(tmp_path):
    root = tiny.make_root(str(tmp_path), limit=0.05)
    with open(os.path.join(root, "bench", "traffic", "mix.json")) as f:
        mix = json.load(f)
    mix["output_len"] = [24, 48]  # enough served positions to rank
    with open(os.path.join(root, "bench", "traffic", "mix.json"), "w") as f:
        json.dump(mix, f)
    s = calibrate.calibrate(root, "tiny.mix", [21, 22, 23], 3)
    assert s["lower"] <= 0.05 < s["upper"]
    assert all(r["program_passes"] for r in s["rows"])
    assert not any(r["control_passes"] for r in s["rows"])


def test_no_chip_no_result(tmp_path):
    """Without a TPU the run exits nonzero and prints no result line."""
    import shutil
    import subprocess
    import sys

    src = os.path.dirname(tiny.BENCH)
    dst = tmp_path / "co"
    shutil.copytree(tiny.BENCH, dst / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(src, "BENCHMARK.json"), dst / "BENCHMARK.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "qwen3-bfly.chat",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=dst, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout
