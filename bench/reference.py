"""Plain float32 forward pass of the served decoder: the reference that
decides ``correct``.

It follows the published Qwen3 layer equations (Qwen/Qwen3-0.6B
``config.json`` and the transformers ``Qwen3`` model): token embedding;
per layer RMSNorm, q/k/v projections, per-head RMSNorm of q and k,
rotary embedding (half split, theta from the file), grouped-query
attention, output projection, residual, RMSNorm, SwiGLU MLP, residual;
final RMSNorm and the LM head.  Every matmul is float32 at precision
HIGHEST.  Departures, each also taken by the program it checks:

* attention keeps only the butterfly pattern's live key tiles: key tile
  ``j`` is read by query tile ``i`` when ``j <= i`` and ``i ^ j`` has at
  most one bit set (tiles of ``tile`` tokens), then the causal mask;
* norm gains are stored as offsets: the gain is ``1 + w``;
* the LM head is its own matrix (the published model ties it to the
  embedding);
* with ``linears == "bpmm"`` each linear is the product of its Monarch
  factors, multiplied out here into a dense matrix.

``control=True`` computes the same pass with every matmul operand rounded
to float8 e4m3 (scaled per row or column): the next precision below the
bfloat16 the configuration serves in.  It is the comparison's control and
never runs inside a benchmark run.

Nothing here imports the program: the weights are the benchmark's own
arrays, read as data.  The building blocks (:func:`mm`, :func:`rms`,
:func:`rope`, :func:`attention`, :func:`pad`) are public for the references
of other models' code under ``bench/models/``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["q8", "mm", "monarch_dense", "butterfly_tiles", "rms", "rope",
           "attention", "pad", "logits_at"]

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn


def q8(x: jax.Array, axis: int) -> jax.Array:
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(a, w, control: bool):
    """a (..., din) @ w (din, dout)."""
    if control:
        a, w = q8(a, -1), q8(w, 0)
    return jnp.matmul(a, w, precision=HI)


def monarch_dense(r: jax.Array, l: jax.Array, din: int, dout: int) -> jax.Array:
    """Dense (din, dout) matrix of one Monarch-factored linear.

    r: (gout, gin, nb, b, b), l: (gout, gin, b, nb, nb).  Input slice g is
    indexed (k, j) with k over nb blocks and j within a block; output slice
    o is indexed (h, a).  The first factor mixes within a block,
    ``u[o,g,k,a] = sum_j r[o,g,k,a,j] x[g,k,j]``; the second mixes across
    blocks, ``y[o,h,a] = sum_g sum_k l[o,g,a,h,k] u[o,g,k,a]``."""
    go, gi, nb, b, _ = r.shape
    w = jnp.einsum("ogahk,ogkaj->gkjoha", l, r, precision=HI)
    return w.reshape(gi * nb * b, go * nb * b)[:din, :dout]


def butterfly_tiles(n_tiles: int, pattern: str) -> np.ndarray:
    """(n_tiles, max_live) key tiles each query tile reads, -1 padded.
    Butterfly: ``j <= i`` with ``popcount(i ^ j) <= 1``; dense: ``j <= i``."""
    rows = []
    for i in range(n_tiles):
        if pattern == "butterfly":
            rows.append([j for j in range(i + 1) if bin(i ^ j).count("1") <= 1])
        elif pattern == "dense":
            rows.append(list(range(i + 1)))
        else:
            raise ValueError(f"no reference for attention pattern {pattern!r}")
    width = max(len(r) for r in rows)
    out = np.full((n_tiles, width), -1, np.int32)
    for i, r in enumerate(rows):
        out[i, : len(r)] = r
    return out


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def rope(x, pos, theta):
    """x (T, H, hd), half-split rotation (transformers' rotate_half)."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (np.arange(0, 2 * half, 2, dtype=np.float32) / (2 * half)))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, table, tile, control):
    """q (T, H, hd), k/v (T, KV, hd), T a multiple of ``tile``; ``table``
    from :func:`butterfly_tiles`."""
    t, h, hd = q.shape
    kvh = k.shape[1]
    g = h // kvh
    nt = t // tile
    qt = q.reshape(nt, tile, kvh, g, hd)
    idx = jnp.asarray(np.maximum(table, 0))
    kt = k.reshape(nt, tile, kvh, hd)[idx]  # (nt, L, tile, KV, hd)
    vt = v.reshape(nt, tile, kvh, hd)[idx]
    if control:
        qt, kt = q8(qt, -1), q8(kt, -1)
    s = jnp.einsum("iqkgd,ilskd->ikgqls", qt, kt, precision=HI) / math.sqrt(hd)
    qpos = np.arange(nt)[:, None, None, None] * tile + np.arange(tile)[None, :, None, None]
    kpos = table[:, None, :, None] * tile + np.arange(tile)[None, None, None, :]
    live = (table[:, None, :, None] >= 0) & (kpos <= qpos)  # (nt, tile, L, tile)
    s = jnp.where(jnp.asarray(live)[:, None, None], s, -jnp.inf)
    lw = s.shape[-2] * s.shape[-1]
    p = jax.nn.softmax(s.reshape(*s.shape[:-2], lw), axis=-1)
    vv = vt.transpose(0, 3, 1, 2, 4).reshape(nt, kvh, lw, hd)  # (nt, KV, L*tile, hd)
    if control:
        p, vv = q8(p, -1), q8(vv, -2)
    o = jnp.einsum("ikgqm,ikmd->iqkgd", p, vv, precision=HI)
    return o.reshape(t, h, hd)


@functools.partial(jax.jit, static_argnames=("shape", "control"))
def _forward(params, tokens, read, *, shape, control):
    (layers, heads, kvh, hd, eps, theta, tile, pattern, linears, dims) = shape
    dims = dict(dims)
    t = tokens.shape[0]
    table = butterfly_tiles(t // tile, pattern)
    pos = jnp.arange(t)
    x = params["embed"][tokens]
    lp = params["layers"]["slot00"]

    def weight(site, name):
        din, dout = dims[name]
        if linears == "dense":
            return site[name]["w"]
        return monarch_dense(site[name]["r"], site[name]["l"], din, dout)

    def layer(x, p):
        a = p["attn"]
        h = rms(x, p["mixer_norm"]["w"], eps)
        q = mm(h, weight(a, "wq"), control).reshape(t, heads, hd)
        k = mm(h, weight(a, "wk"), control).reshape(t, kvh, hd)
        v = mm(h, weight(a, "wv"), control).reshape(t, kvh, hd)
        q = rope(rms(q, a["q_norm"], eps), pos, theta)
        k = rope(rms(k, a["k_norm"], eps), pos, theta)
        o = attention(q, k, v, table, tile, control).reshape(t, heads * hd)
        x = x + mm(o, weight(a, "wo"), control)
        f = p["ffn"]
        h = rms(x, p["ffn_norm"]["w"], eps)
        u = jax.nn.silu(mm(h, weight(f, "w1"), control)) * mm(h, weight(f, "w3"), control)
        return x + mm(u, weight(f, "w2"), control), None

    x, _ = jax.lax.scan(layer, x, lp)
    x = rms(x[read], params["final_norm"]["w"][0], eps)
    return mm(x, params["head"], control)


def _bucket(n: int, floor: int) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


def pad(tokens: np.ndarray, read: np.ndarray, tile: int) -> tuple[jax.Array, jax.Array]:
    """``tokens`` and ``read`` padded to powers of two, so that few lengths
    compile: the padding sits after every read position, where causal
    attention never sees it, and repeats the last read position."""
    t = _bucket(len(tokens), max(tile, 128))
    tok = np.zeros(t, np.int32)
    tok[: len(tokens)] = tokens
    r = _bucket(len(read), 8)
    rd = np.full(r, read[-1], np.int32)
    rd[: len(read)] = read
    return jnp.asarray(tok), jnp.asarray(rd)


def logits_at(params, cfg: dict, tokens: np.ndarray, read: np.ndarray,
              control: bool = False) -> np.ndarray:
    """Float32 logits at positions ``read`` of the sequence ``tokens``,
    from the configuration file ``cfg`` and the weights ``params``
    (lengths padded by :func:`pad`)."""
    s = cfg["serving"]
    tile = s["tile"]
    d, heads, hd = cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"]
    kvh, ff = cfg["num_key_value_heads"], cfg["intermediate_size"]
    dims = (("wq", (d, heads * hd)), ("wk", (d, kvh * hd)), ("wv", (d, kvh * hd)),
            ("wo", (heads * hd, d)), ("w1", (d, ff)), ("w3", (d, ff)),
            ("w2", (ff, d)))
    shape = (cfg["num_hidden_layers"], heads, kvh, hd, float(cfg["rms_norm_eps"]),
             float(cfg["rope_theta"]), tile, s["attn_pattern"], s["linears"], dims)
    tok, rd = pad(tokens, read, tile)
    out = _forward(params, tok, rd, shape=shape, control=control)
    return np.asarray(out, np.float32)[: len(read)]

