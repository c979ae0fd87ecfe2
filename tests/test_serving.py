"""Prefill + decode == full forward, for every cache-bearing family (f32)."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import registry
from repro.models import model as M
from repro.models import transformer as tf
from repro.models.layers import Runtime

RT = Runtime(mesh=None)
B, S = 2, 16


def _f32(cfg):
    # capacity_factor high so the train-mode reference forward is dropless
    # too (decode uses exact dropless dispatch)
    return dataclasses.replace(cfg, dtype="float32", capacity_factor=8.0)


@pytest.mark.parametrize(
    "arch", ["yi-6b", "mamba2-130m", "jamba-1.5-large", "whisper-base", "mixtral-8x22b"]
)
def test_decode_matches_forward(arch):
    cfg = _f32(registry.get(arch, reduced=True))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": tokens}
    pb = {"tokens": tokens[:, :-1]}
    if cfg.family == "encdec":
        frames = jax.random.normal(jax.random.PRNGKey(2), (B, cfg.enc_seq, cfg.d_model))
        batch["frames"] = frames
        pb["frames"] = frames

    full, _ = tf.forward(params, cfg, batch, RT, mode="train")
    lp, caches = tf.prefill(params, cfg, pb, RT, cache_len=S)
    ld, _ = tf.decode_step(params, cfg, caches, tokens[:, -1:], jnp.int32(S - 1), RT)

    tol = 2e-4 * float(jnp.max(jnp.abs(full)))
    assert float(jnp.max(jnp.abs(lp - full[:, -2]))) < tol, "prefill logits diverge"
    assert float(jnp.max(jnp.abs(ld - full[:, -1]))) < tol, "decode logits diverge"


def test_multi_step_decode_matches_forward():
    cfg = _f32(registry.get("yi-6b", reduced=True))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    full, _ = tf.forward(params, cfg, {"tokens": tokens}, RT)

    plen = S - 4
    _, caches = tf.prefill(params, cfg, {"tokens": tokens[:, :plen]}, RT, cache_len=S)
    for j in range(4):
        ld, caches = tf.decode_step(
            params, cfg, caches, tokens[:, plen + j : plen + j + 1], jnp.int32(plen + j), RT
        )
        err = float(jnp.max(jnp.abs(ld - full[:, plen + j])))
        assert err < 2e-4 * float(jnp.max(jnp.abs(full))), f"step {j}: {err}"


def test_serve_loop_generates():
    import numpy as np

    from repro.launch.mesh import make_local_mesh
    from repro.launch.serve import Request, ServeLoop

    cfg = _f32(registry.get("qwen3-0.6b", reduced=True))
    mesh = make_local_mesh()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    loop = ServeLoop(cfg, mesh, params, batch=2, cache_len=32)
    reqs = [
        Request(uid=0, prompt=np.array([5, 6, 7], np.int32), max_new=4),
        Request(uid=1, prompt=np.array([9, 3], np.int32), max_new=3),
    ]
    done = loop.run(reqs)
    assert len(done[0].generated) == 4
    assert len(done[1].generated) == 3
    assert all(0 <= t < cfg.vocab for r in done for t in r.generated)


# --------------------------------------------------------------------------
# Ragged continuous batching: sliding-window ring masking + mixed-length
# parity against isolated decoding
# --------------------------------------------------------------------------


@pytest.mark.parametrize("impl", ["xla_chunked", "flash_kernel"])
def test_sliding_window_decode_matches_forward(impl):
    """Ring-cache decode at pos < window: unwritten ring rows must be masked.

    cache_len > prompt leaves zero-initialised ring rows; before the live-KV
    mask those scored e^0 in the softmax and decode diverged from forward.
    The loop then crosses pos >= window, covering the ring-wrap phase too.
    """
    from repro.core.attention import AttentionSpec

    cfg = dataclasses.replace(
        _f32(registry.get("qwen3-0.6b", reduced=True)),
        sliding_window=10,
        attention=AttentionSpec(impl=impl),
    )
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    full, _ = tf.forward(params, cfg, {"tokens": tokens}, RT)
    plen = 6  # < window, and cache_len=24 > plen: ring rows 6..9 start unwritten
    _, caches = tf.prefill(params, cfg, {"tokens": tokens[:, :plen]}, RT, cache_len=24)
    tol = 2e-4 * float(jnp.max(jnp.abs(full)))
    for j in range(S - plen):
        ld, caches = tf.decode_step(
            params, cfg, caches, tokens[:, plen + j : plen + j + 1],
            jnp.int32(plen + j), RT,
        )
        err = float(jnp.max(jnp.abs(ld - full[:, plen + j])))
        assert err < tol, f"step {j} (pos {plen + j}): {err}"


def _reference_greedy(cfg, params, prompt, max_new, cache_len, extras=None):
    """Greedy-decode one request in isolation (eager batch-1 prefill+decode)."""
    import numpy as np

    batch = {"tokens": jnp.asarray(np.asarray(prompt)[None, :])}
    for key, val in (extras or {}).items():
        batch[key] = jnp.asarray(val)[None]
    logits, caches = tf.prefill(params, cfg, batch, RT, cache_len=cache_len)
    nxt = int(jnp.argmax(logits[0]))
    out = [nxt]
    for j in range(max_new - 1):
        logits, caches = tf.decode_step(
            params, cfg, caches, jnp.asarray([[nxt]], jnp.int32),
            jnp.int32(len(prompt) + j), RT,
        )
        nxt = int(jnp.argmax(logits[0]))
        out.append(nxt)
    return out


# arch, cfg tweaks, attn impl — GQA, sliding window (pos < window included),
# and encoder-decoder cross-attention decode
RAGGED_CASES = [
    ("qwen3-0.6b", {}, "xla_chunked"),
    ("qwen3-0.6b", {}, "flash_kernel"),
    ("qwen3-0.6b", {"sliding_window": 10}, "xla_chunked"),
    ("qwen3-0.6b", {"sliding_window": 10}, "flash_kernel"),
    ("whisper-base", {}, "xla_chunked"),
]


@pytest.mark.parametrize("arch,tweaks,impl", RAGGED_CASES)
def test_ragged_batch_matches_isolated(arch, tweaks, impl):
    """A mixed-length batch through the continuous engine generates exactly
    what each request generates when decoded alone (same params, greedy)."""
    import numpy as np

    from repro.core.attention import AttentionSpec
    from repro.launch.mesh import make_local_mesh
    from repro.launch.serve import Request, ServeLoop

    cfg = dataclasses.replace(
        _f32(registry.get(arch, reduced=True)),
        attention=AttentionSpec(impl=impl),
        **tweaks,
    )
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    extras = {}
    if cfg.family == "encdec":
        extras = {
            "frames": jax.random.normal(
                jax.random.PRNGKey(2), (cfg.enc_seq, cfg.d_model), jnp.float32
            )
        }
    # distinct prompt lengths and max_new; window cases decode past pos=window
    reqs = [
        Request(
            uid=i,
            prompt=rng.integers(0, cfg.vocab, size=ln).astype(np.int32),
            max_new=mn,
            extras=dict(extras),
        )
        for i, (ln, mn) in enumerate([(7, 8), (3, 5), (12, 3)])
    ]
    loop = ServeLoop(cfg, make_local_mesh(), params, batch=3, cache_len=24)
    done = loop.run(reqs)
    for r in done:
        ref = _reference_greedy(
            cfg, params, r.prompt, r.max_new, 24, extras=extras
        )
        assert r.generated == ref, f"uid {r.uid}: {r.generated} != {ref}"


def test_serve_loop_rejects_stateful_mixers():
    """Bucketed right-pad prefill would fold pad tokens into SSM state —
    the engine must refuse loudly, not generate silently-wrong streams."""
    from repro.launch.mesh import make_local_mesh
    from repro.launch.serve import ServeLoop

    cfg = _f32(registry.get("mamba2-130m", reduced=True))
    with pytest.raises(ValueError, match="attention-only"):
        ServeLoop(cfg, make_local_mesh(), None, batch=2, cache_len=32)


# --------------------------------------------------------------------------
# Chunked-prefill mixed-step engine
# --------------------------------------------------------------------------


def test_next_bucket_boundaries():
    """Buckets must stay a bounded set (powers of two or exactly the cap) so
    the jit shape cache is bounded; n > cap is a caller bug, not a shape."""
    from repro.launch.serve import _next_bucket

    assert _next_bucket(1, 64) == 8
    assert _next_bucket(8, 64) == 8
    assert _next_bucket(9, 64) == 16
    assert _next_bucket(33, 64) == 64
    assert _next_bucket(64, 64) == 64
    # non-power-of-two cap: n landing between the cap and the next power of
    # two must clamp to the cap, never leak arbitrary n into the jit cache
    assert _next_bucket(20, 24) == 24
    assert _next_bucket(24, 24) == 24
    assert _next_bucket(5, 24) == 8
    with pytest.raises(ValueError, match="exceeds cap"):
        _next_bucket(25, 24)
    vals = {_next_bucket(n, 100) for n in range(1, 101)}
    assert vals <= {8, 16, 32, 64, 100}


# pattern, pattern_arg, impl, cache_len, (prompt_len, max_new) list, chunk.
# dense/window run at small shapes; butterfly needs cache_len >= 512 so the
# kv-tile grid (128-wide tiles) actually has dead tiles to skip.  qwen3 is
# GQA (4 heads over 2 kv heads) throughout.
CHUNKED_CASES = [
    ("dense", None, "xla_chunked", 64, [(17, 6), (3, 5), (41, 3)], 8),
    ("dense", None, "flash_kernel", 64, [(17, 6), (3, 5), (41, 3)], 8),
    ("window", 16, "xla_chunked", 64, [(17, 6), (3, 5), (41, 3)], 8),
    ("window", 16, "flash_kernel", 64, [(17, 6), (3, 5), (41, 3)], 8),
    ("butterfly", None, "xla_chunked", 512, [(300, 5), (7, 6), (150, 3)], 32),
    ("butterfly", None, "flash_kernel", 512, [(300, 4), (7, 4)], 32),
]


@pytest.mark.parametrize("pattern,arg,impl,cache_len,lens,chunk", CHUNKED_CASES)
def test_chunked_engine_matches_admission_engine(
    pattern, arg, impl, cache_len, lens, chunk
):
    """The mixed-step engine must be token-identical to the admission-prefill
    engine (and to isolated greedy decoding) on interleaved long/short
    prompts — chunked prefill changes the schedule, never the math."""
    import numpy as np

    from repro.core.attention import AttentionSpec
    from repro.launch.mesh import make_local_mesh
    from repro.launch.serve import Request, ServeLoop

    cfg = dataclasses.replace(
        _f32(registry.get("qwen3-0.6b", reduced=True)),
        attention=AttentionSpec(impl=impl, pattern=pattern, pattern_arg=arg),
    )
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab, size=ln).astype(np.int32) for ln, _ in lens]

    def mk():
        return [
            Request(uid=i, prompt=p, max_new=mn)
            for i, (p, (_, mn)) in enumerate(zip(prompts, lens))
        ]

    mesh = make_local_mesh()
    ref = ServeLoop(cfg, mesh, params, batch=2, cache_len=cache_len).run(mk())
    ch = ServeLoop(
        cfg, mesh, params, batch=2, cache_len=cache_len, chunked=True,
        chunk_size=chunk,
    ).run(mk())
    for r1, r2 in zip(ref, ch):
        assert r2.generated == r1.generated, f"uid {r1.uid}"
    if pattern == "dense":  # the engines also match isolated decoding
        for r in ch:
            assert r.generated == _reference_greedy(
                cfg, params, r.prompt, r.max_new, cache_len
            ), f"uid {r.uid} vs isolated"


def test_host_state_reaches_device_as_snapshot():
    """Scheduler state goes to the device as a snapshot: the engine's
    in-place update right after a dispatch (``pos[slot] += 1``) must not
    reach a step still queued behind earlier work.  The CPU backend aliases
    64-byte-aligned numpy buffers, so without the host copy greedy streams
    changed from run to run."""
    import numpy as np

    from repro.launch.serving.queueing import _to_device

    busy = jax.jit(lambda a: (a @ a) @ a)
    step = jax.jit(lambda a, x: x + 0 * a[0, 0].astype(jnp.int32))
    a = jnp.ones((512, 512))
    buf = np.zeros(8 + 16, np.int32)
    off = (-buf.ctypes.data % 64) // 4
    pos = buf[off : off + 8]  # 64-byte aligned, as the allocator may hand out
    for _ in range(8):
        pos[:] = 0
        out = step(busy(a), _to_device(pos))
        pos += 1
        assert np.asarray(out).tolist() == [0] * 8


def test_chunked_decode_never_stalls_on_admission():
    """A long prompt arriving mid-decode must stream in chunks WHILE the live
    decode rows keep sampling: zero decode stalls, overlap steps observed,
    and generations still token-identical to the admission engine."""
    import numpy as np

    from repro.launch.mesh import make_local_mesh
    from repro.launch.serve import Request, ServeLoop

    cfg = _f32(registry.get("qwen3-0.6b", reduced=True))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(4)
    short = [rng.integers(0, cfg.vocab, size=4).astype(np.int32) for _ in range(2)]
    long_p = rng.integers(0, cfg.vocab, size=90).astype(np.int32)

    def mk():
        rs = [Request(uid=i, prompt=p, max_new=12) for i, p in enumerate(short)]
        rs.append(Request(uid=2, prompt=long_p, max_new=3, arrival=2))
        return rs

    mesh = make_local_mesh()
    loop = ServeLoop(
        cfg, mesh, params, batch=3, cache_len=128, chunked=True, chunk_size=8
    )
    done = loop.run(mk())
    assert loop.stats["decode_stall_steps"] == 0
    # the long prompt needs ceil(90/8) > 11 chunk steps; the short requests'
    # 12 decode steps must overlap them rather than wait
    assert loop.stats["overlap_steps"] >= 3
    assert loop.stats["prefill_calls"] == 0
    ref = ServeLoop(cfg, mesh, params, batch=3, cache_len=128).run(mk())
    for r1, r2 in zip(ref, done):
        assert r2.generated == r1.generated, f"uid {r1.uid}"


def test_kv_live_bucket_boundary_butterfly_decode():
    """Regression: butterfly decode with the live cache bucketed at
    ``hot`` one above a power of two (cur_len 129 -> kv_live 256 on a 512
    cache) must match the untruncated decode — the per-row live-tile tables
    rebuilt at the truncated length may not change liveness."""
    from repro.core.attention import AttentionSpec
    from repro.models.layers import run_decode_attention

    key = jax.random.PRNGKey(2)
    b, h, kv, hd, cache = 2, 4, 2, 16, 512
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, h, hd), jnp.float32)
    kc = jax.random.normal(kk, (b, cache, kv, hd), jnp.float32)
    vc = jax.random.normal(kv_, (b, cache, kv, hd), jnp.float32)
    cur = jnp.asarray([129, 65], jnp.int32)  # one above a power of two
    for impl in ("xla_chunked", "flash_kernel"):
        spec = AttentionSpec(impl=impl, pattern="butterfly")
        full = run_decode_attention(q, kc, vc, cur, spec=spec)
        bucketed = run_decode_attention(q, kc, vc, cur, spec=spec, kv_live=256)
        err = float(jnp.max(jnp.abs(full - bucketed)))
        assert err < 1e-5, f"{impl}: kv_live truncation diverged by {err}"


@pytest.mark.parametrize("pattern", ["dense", "butterfly"])
@pytest.mark.parametrize("impl", ["xla_chunked", "flash_kernel"])
def test_chunk_attention_matches_prefill_rows(pattern, impl):
    """A mid-sequence chunk of queries over the shared cache must equal the
    same rows of a full prefill — per-query pattern liveness (each query's
    own q-tile row), causal frontier, GQA grouping all exact."""
    import numpy as np

    from repro.core.attention import AttentionSpec
    from repro.models.layers import run_attention, run_chunk_attention

    key = jax.random.PRNGKey(3)
    b, s, h, kvh, hd, c = 2, 512, 4, 2, 16, 96
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, hd), jnp.float32)
    k = jax.random.normal(kk, (b, s, kvh, hd), jnp.float32)
    v = jax.random.normal(kv_, (b, s, kvh, hd), jnp.float32)
    spec = AttentionSpec(impl=impl, pattern=pattern)
    full = run_attention(q, k, v, spec=spec, causal=True)
    start = np.asarray([200, 64], np.int32)  # not tile-aligned on row 0
    qc = jnp.stack([q[i, p : p + c] for i, p in enumerate(start)])
    out = run_chunk_attention(
        qc, k, v, jnp.asarray(start), jnp.full((b,), c, jnp.int32), spec=spec
    )
    ref = jnp.stack([full[i, p : p + c] for i, p in enumerate(start)])
    tol = 2e-5 * float(jnp.max(jnp.abs(ref)))
    err = float(jnp.max(jnp.abs(out - ref)))
    assert err < tol, f"{impl}/{pattern}: chunk rows diverge by {err}"


def test_serve_admit_evict_mid_stream():
    """More requests than slots: short requests exit, queued ones are admitted
    into the freed slot mid-stream, and every stream still matches isolation."""
    import numpy as np

    from repro.launch.mesh import make_local_mesh
    from repro.launch.serve import Request, ServeLoop

    cfg = _f32(registry.get("qwen3-0.6b", reduced=True))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    reqs = [
        Request(uid=i, prompt=rng.integers(0, cfg.vocab, size=ln).astype(np.int32),
                max_new=mn)
        for i, (ln, mn) in enumerate([(4, 2), (6, 7), (3, 1), (9, 4), (2, 5)])
    ]
    loop = ServeLoop(cfg, make_local_mesh(), params, batch=2, cache_len=32)
    done = loop.run(reqs)
    # with 2 slots and a 7-step stream in flight, uid 3/4 can only complete
    # via mid-stream admission into evicted slots
    assert loop.stats["prefill_calls"] == 5
    assert loop.stats["decode_steps"] < sum(r.max_new for r in reqs)
    for r in done:
        assert len(r.generated) == r.max_new
        ref = _reference_greedy(cfg, params, r.prompt, r.max_new, 32)
        assert r.generated == ref, f"uid {r.uid}"
