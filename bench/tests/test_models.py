"""A configuration's model code is a file under ``bench/models/`` that the
harness loads by the name the configuration gives: the qwen3 module serves
the weights and logits the benchmark served before it existed; a second
family's module, added as files, brings its own parameter tree; a missing
one fails at load.  Also the cells' metric lists and the mix's loop keys."""

import copy
import json
import os

import jax
import numpy as np
import pytest

import counts
import harness
import reference
import tiny
import weights

ROOT = os.path.dirname(tiny.BENCH)
ACCEPTED = ["qwen3-bfly.longctx", "qwen3-bfly.chat", "qwen3-bpmm.prefill"]
LISTED = ["decode_occupancy", "pool_peak_frac", "serve_mfu", "device_idle",
          "peak_hbm_frac"]


def _toy(config: str) -> dict:
    with open(os.path.join(tiny.BENCH, "configs", f"{config}.json")) as f:
        return dict(json.load(f), **tiny.TINY)


def _paths(tree) -> set:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p) for p, _ in flat}


@pytest.mark.parametrize("config", ["qwen3-0.6b-bfly", "qwen3-0.6b-bpmm"])
def test_qwen3_module_serves_the_same_weights_and_logits(config):
    cfg = _toy(config)
    model = harness.load_model(ROOT, cfg)
    ms = model.shape(cfg)
    assert ms == counts.ModelShape.from_config(cfg)
    got, want = model.make_params(ms, 2**31 + 5), weights.make_params(ms, 2**31 + 5)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    tokens = np.random.default_rng(4).integers(0, ms.vocab, 300, dtype=np.int32)
    read = np.arange(150, 300)
    for control in (False, True):
        np.testing.assert_array_equal(
            model.logits_at(got, cfg, tokens, read, control=control),
            reference.logits_at(want, cfg, tokens, read, control=control))


def test_second_family_brings_its_own_tree(tmp_path):
    root = tiny.use_dense_gqa(tiny.make_root(str(tmp_path)))
    _, _, cfg, _, model = harness.load_cell(root, "tiny.mix")
    assert model.__file__ == os.path.join(root, "bench", "models", "dense_gqa.py")
    qwen3 = harness.load_model(root, _toy("qwen3-0.6b-bfly"))
    mine = _paths(model.make_params(model.shape(cfg), 3))
    theirs = _paths(qwen3.make_params(qwen3.shape(cfg), 3))
    assert theirs - mine == {"['layers']['slot00']['attn']['q_norm']",
                             "['layers']['slot00']['attn']['k_norm']"}
    assert mine < theirs
    from repro.models import model as M

    harness.check_layout(model.make_params(model.shape(cfg), 3),
                         M.abstract_params(model.program_config(cfg)))
    with pytest.raises(RuntimeError, match="parameter layout"):
        harness.check_layout(qwen3.make_params(qwen3.shape(cfg), 3),
                             M.abstract_params(model.program_config(cfg)))


def test_missing_model_code_fails_at_load(tmp_path):
    root = tiny.make_root(str(tmp_path))
    path = os.path.join(root, "bench", "configs", "tiny.json")
    with open(path) as f:
        cfg = json.load(f)
    with open(path, "w") as f:
        json.dump(dict(cfg, model_code="nonesuch"), f)
    missing = os.path.join(root, "bench", "models", "nonesuch.py")
    with pytest.raises(FileNotFoundError) as e:
        harness.load_cell(root, "tiny.mix")
    assert missing in str(e.value)


@pytest.mark.parametrize("per_layer", [False, True])
@pytest.mark.parametrize("cell", ACCEPTED)
def test_workload_lists_keep_each_cells_metrics(cell, per_layer):
    """The five per-layer metrics that had no ``workloads`` list name the
    three accepted cells; each cell reports the metrics it did without."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    before = copy.deepcopy(bench)
    for m in before["per_layer"]:
        if m["name"] in LISTED:
            assert m.pop("workloads") == ACCEPTED
    entry = {w["name"]: w for w in bench["workloads"]}[cell]
    names = [m["name"] for m in harness.cell_metrics(bench, entry, per_layer)]
    assert names == [m["name"] for m in harness.cell_metrics(before, entry, per_layer)]
    if per_layer:
        assert set(LISTED) <= set(names)


def test_mix_loop_keys_reach_the_loop(tmp_path):
    root = tiny.make_root(str(tmp_path))
    path = os.path.join(root, "bench", "traffic", "mix.json")
    _, _, cfg, mix, model = harness.load_cell(root, "tiny.mix")
    devices = jax.devices()[:1]
    sess = harness.Session(cfg, mix, 1, devices, model)
    assert sess.loop.chunk_budget == mix["serving"]["chunk"]
    mix["serving"]["loop"] = {"chunk_budget": 64}
    with open(path, "w") as f:
        json.dump(mix, f)
    _, _, cfg, mix, model = harness.load_cell(root, "tiny.mix")
    assert harness.Session(cfg, mix, 1, devices, model).loop.chunk_budget == 64
