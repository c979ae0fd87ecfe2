"""Sharding rules + multi-device integration (subprocess with fake devices)."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.distributed.sharding import ParamSpec, spec_for
from repro.launch.mesh import make_mesh

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _mesh(shape, names):
    return make_mesh(shape, names)


def test_spec_for_divisibility_fallback():
    mesh = _mesh((1, 1), ("data", "model"))
    # single-device mesh: everything replicates but specs still build
    assert spec_for((64, 64), ("fsdp", "tp"), mesh) is not None


def test_spec_for_rules():
    import jax.sharding as js

    mesh = _mesh((1, 1), ("data", "model"))
    p = spec_for((56, 128), ("tp", None), mesh)  # 56 % 1 == 0 -> sharded ('model' size 1)
    assert isinstance(p, js.PartitionSpec)


def _run_subprocess(body: str, ndev: int = 8) -> str:
    """Run a snippet under a forced multi-device CPU backend."""
    code = textwrap.dedent(body)
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["PYTHONPATH"] = SRC
    # force CPU: the fake-device flag only applies to the host platform, and
    # letting jax probe a TPU backend here hangs for minutes in CI containers
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env, timeout=600
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_spec_for_fallbacks_multidevice():
    out = _run_subprocess("""
        import jax
        from repro.distributed.sharding import spec_for
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        # 56 % 4 == 0 -> sharded; 54 % 4 != 0 -> replicated fallback
        print(spec_for((56, 10), ("tp", None), mesh))
        print(spec_for((54, 10), ("tp", None), mesh))
        # batch spreads over (pod, data) only when both divide
        mesh3 = make_mesh((2, 2, 2), ("pod", "data", "model"))
        print(spec_for((8, 16), ("batch", None), mesh3))
        print(spec_for((2, 16), ("batch", None), mesh3))
        print(spec_for((1, 16), ("batch", None), mesh3))
    """)
    lines = out.strip().splitlines()
    assert "model" in lines[0]
    assert "model" not in lines[1]
    assert "pod" in lines[2] and "data" in lines[2]
    assert "pod" in lines[3] and "data" not in lines[3]
    assert "pod" not in lines[4]


def test_train_step_runs_sharded():
    """Real sharded train step on a 2x4 fake mesh: loss finite, params update."""
    out = _run_subprocess("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs import registry
        from repro.data.pipeline import DataConfig, global_batch
        from repro.launch.mesh import make_mesh
        from repro.launch.train import TrainHParams, make_train_step, init_train_state, train_state_shardings
        cfg = dataclasses.replace(registry.get("qwen3-0.6b", reduced=True),
                                  n_heads=4, n_kv_heads=4, attn_chunk=16)
        mesh = make_mesh((2, 4), ("data", "model"))
        hp = TrainHParams(peak_lr=1e-3, warmup=1, total_steps=4)
        step, st_sh, _ = make_train_step(cfg, mesh, hp)
        with mesh:
            state = init_train_state(cfg, hp, jax.random.PRNGKey(0))
            state = jax.tree.map(jax.device_put, state, st_sh)
        dc = DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=8)
        losses = []
        for s in range(3):
            batch = global_batch(dc, s, mesh)
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
        assert all(np.isfinite(losses)), losses
        assert int(state["step"]) == 3
        print("LOSSES", losses)
    """)
    assert "LOSSES" in out


def test_gpipe_pipeline_parallelism():
    """GPipe over an 8-deep pipe axis == sequential stage application."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline import gpipe
        from repro.launch.mesh import make_mesh
        S, M, mb, d = 8, 16, 4, 16
        mesh = make_mesh((S,), ("pipe",))
        keys = jax.random.split(jax.random.PRNGKey(0), S)
        params = {"w": jnp.stack([jax.random.normal(k, (d, d)) / np.sqrt(d) for k in keys]),
                  "b": jnp.zeros((S, d))}
        xs = jax.random.normal(jax.random.PRNGKey(1), (M, mb, d))
        stage = lambda p, x: jnp.tanh(x @ p["w"] + p["b"])
        with mesh:
            y = gpipe(stage, params, xs, mesh, axis="pipe")
        # sequential reference
        ref = xs
        for i in range(S):
            ref = stage({"w": params["w"][i], "b": params["b"][i]}, ref)
        err = float(jnp.max(jnp.abs(y - ref)))
        assert err < 1e-5, err
        print("GPIPE-OK", err)
    """)
    assert "GPIPE-OK" in out


def test_wire_compression_shard_map():
    """int8 EF all-reduce over a pod axis inside shard_map: grads match the
    uncompressed mean within one quantisation step."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.optim.compression import psum_compressed
        mesh = make_mesh((4, 2), ("pod", "data"))
        g = jax.random.normal(jax.random.PRNGKey(0), (4, 64))  # per-pod grads
        err = jnp.zeros((4, 64))
        def f(g, e):
            mean, new_e = psum_compressed({"g": g[0]}, {"g": e[0]}, "pod")
            return mean["g"], new_e["g"][None]
        fn = jax.shard_map(f, mesh=mesh, in_specs=(P("pod"), P("pod")),
                       out_specs=(P(), P("pod")), axis_names={"pod"})
        with mesh:
            mean, new_err = fn(g, err)
        ref = g.mean(0)
        err_bound = float(jnp.abs(g).max()) / 127 + 1e-6
        assert float(jnp.abs(mean - ref).max()) <= err_bound
        print("COMPRESS-OK")
    """)
    assert "COMPRESS-OK" in out


def test_param_shardings_cover_all_leaves():
    from repro.configs import registry
    from repro.models import model as M
    from repro.distributed import sharding as shd

    mesh = _mesh((1, 1), ("data", "model"))
    for arch in ["yi-6b", "jamba-1.5-large", "whisper-base"]:
        cfg = registry.get(arch, reduced=True)
        specs = M.build_specs(cfg)
        sh = shd.sharding_tree(specs, mesh)
        n_specs = len(jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, ParamSpec)))
        n_sh = len(jax.tree.leaves(sh))
        assert n_specs == n_sh > 0


def test_flash_kernel_refused_on_multidevice_mesh():
    """A flash_kernel spec on a mesh of more than one device raises and names
    the form to ask for, instead of quietly running the XLA form."""
    out = _run_subprocess("""
        import jax.numpy as jnp
        from repro.core.attention import AttentionSpec
        from repro.launch.mesh import make_mesh
        from repro.models.layers import Runtime, run_attention
        rt = Runtime(mesh=make_mesh((2, 1), ("data", "model")))
        q = jnp.ones((2, 8, 2, 16))
        try:
            run_attention(q, q, q, spec=AttentionSpec(impl="flash_kernel"), rt=rt)
        except ValueError as e:
            assert "xla_chunked" in str(e), e
            print("REFUSED")
        y = run_attention(q, q, q, spec=AttentionSpec(impl="xla_chunked"), rt=rt)
        assert y.shape == q.shape
    """, ndev=2)
    assert "REFUSED" in out


def test_chip_smoke_refuses_cpu():
    """chip_smoke.py exits nonzero on a host without a TPU, names the
    platform it found, and prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    script = os.path.join(os.path.dirname(__file__), "..", "chip_smoke.py")
    out = subprocess.run(
        [sys.executable, script], capture_output=True, text=True, env=env,
        timeout=300,
    )
    assert out.returncode != 0
    assert "'cpu'" in out.stderr, out.stderr[-2000:]
    assert '"ok"' not in out.stdout
