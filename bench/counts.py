"""Operations and bytes of the served work, from shapes alone.

The yardstick for the roofline and utilization metrics: what the algorithm
needs, whatever implements it.  Attention counts the pattern-live
(query, key) pairs at token level and reads each live K/V row once per
kv head per call, plus one read of q and one write of o.  A BPMM linear
counts its two Monarch super-stages per token at the factor shapes, reads
its factors once per call (bf16) and each token's input and output once.
Padding, recomputation and layout copies are not work.

Everything here is plain Python on numbers taken from a configuration
file; nothing is imported from the program under test.
"""

from __future__ import annotations

import dataclasses
import math

__all__ = [
    "ModelShape",
    "live_keys",
    "chunk_live_rows",
    "attention_call",
    "request_attention",
    "bpmm_plan",
    "linear_sites",
    "linear_flops_per_token",
    "bpmm_weight_bytes",
    "bpmm_io_bytes_per_token",
    "model_flops",
]


@dataclasses.dataclass(frozen=True)
class ModelShape:
    """The sizes the counts need, read from a configuration file."""

    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    pattern: str  # "butterfly" | "dense" (causal)
    tile: int  # pattern tile, tokens (q tile == kv tile == page)
    linears: str  # "dense" | "bpmm"
    max_block: int = 512
    max_piece: int = 8192

    @classmethod
    def from_config(cls, cfg: dict) -> "ModelShape":
        s = cfg["serving"]
        return cls(
            layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
            heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
            pattern=s["attn_pattern"], tile=s["tile"], linears=s["linears"],
            max_block=s.get("bpmm_max_block", 512),
            max_piece=s.get("bpmm_max_piece", 8192),
        )


def _popcount(x: int) -> int:
    return bin(x).count("1")


def _live_tiles(i: int, pattern: str) -> list[int]:
    """Key tiles at or before query tile ``i`` that the pattern keeps.
    Butterfly: ``j`` differs from ``i`` in at most one bit, and ``j <= i``
    means that bit is set in ``i``: ``i`` itself and ``i - 2**m``."""
    if pattern == "dense":
        return list(range(i + 1))
    if pattern == "butterfly":
        return [i] + [i - (1 << m) for m in range(i.bit_length()) if i >> m & 1]
    raise ValueError(f"no count for attention pattern {pattern!r}")


def live_keys(p: int, tile: int, pattern: str) -> int:
    """Keys the query at position ``p`` attends: causal and pattern-live."""
    i = p // tile
    n_full = len(_live_tiles(i, pattern)) - 1  # every tile but the diagonal
    return n_full * tile + p % tile + 1


def chunk_live_rows(start: int, end: int, tile: int, pattern: str) -> int:
    """Distinct key rows that any query in ``[start, end)`` attends."""
    tiles: set[int] = set()
    for i in range(start // tile, (end - 1) // tile + 1):
        tiles.update(_live_tiles(i, pattern))
    frontier = (end - 1) // tile
    return sum(tile for j in tiles if j != frontier) + (end - 1) % tile + 1


def attention_call(ms: ModelShape, start: int, end: int, rows: int,
                   dbytes: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one layer's attention for queries ``[start, end)``
    reading ``rows`` distinct key rows."""
    pairs = sum(live_keys(p, ms.tile, ms.pattern) for p in range(start, end))
    flops = 4.0 * ms.head_dim * ms.heads * pairs
    kv = 2.0 * ms.kv_heads * ms.head_dim * dbytes * rows
    qo = 2.0 * (end - start) * ms.heads * ms.head_dim * dbytes
    return flops, kv + qo


def request_attention(ms: ModelShape, prompt: int, max_new: int, chunk: int):
    """Attention work of one request over all layers: a list of
    (FLOPs, bytes) per prefill chunk call, and the (FLOPs, bytes) summed
    over its decode positions.  Chunks start at 0 and advance by ``chunk``;
    decode queries sit at ``prompt .. prompt + max_new - 2``."""
    calls = []
    for s in range(0, prompt, chunk):
        e = min(s + chunk, prompt)
        f, b = attention_call(ms, s, e, chunk_live_rows(s, e, ms.tile, ms.pattern))
        calls.append((f * ms.layers, b * ms.layers))
    df = db = 0.0
    for p in range(prompt, prompt + max_new - 1):
        f, b = attention_call(ms, p, p + 1, live_keys(p, ms.tile, ms.pattern))
        df += f
        db += b
    return calls, (df * ms.layers, db * ms.layers)


def bpmm_plan(din: int, dout: int, max_block: int = 512,
              max_piece: int = 8192) -> dict:
    """Square-piece slicing and the Monarch block split of one linear:
    piece = the largest power of two <= min(din, dout) (capped), padded
    to a gin x gout grid; block b = 2**p with p ~ log2(piece)/2, moved until
    both b and piece/b fit ``max_block``."""
    piece = min(1 << int(math.floor(math.log2(min(din, dout)))), max_piece)
    m = piece.bit_length() - 1
    p = (m + 1) // 2
    while (1 << p) > max_block:
        p -= 1
    while piece // (1 << p) > max_block:
        p += 1
    b = 1 << p
    return {"piece": piece, "gin": -(-din // piece), "gout": -(-dout // piece),
            "b": b, "nb": piece // b}


def linear_sites(ms: ModelShape) -> list[tuple[str, int, int]]:
    """(name, din, dout) of every linear in one decoder layer."""
    d, q, kv = ms.d_model, ms.heads * ms.head_dim, ms.kv_heads * ms.head_dim
    return [("wq", d, q), ("wk", d, kv), ("wv", d, kv), ("wo", q, d),
            ("w1", d, ms.d_ff), ("w3", d, ms.d_ff), ("w2", ms.d_ff, d)]


def _monarch_params(pl: dict) -> int:
    return pl["nb"] * pl["b"] * pl["b"] + pl["b"] * pl["nb"] * pl["nb"]


def linear_flops_per_token(ms: ModelShape) -> float:
    """FLOPs of one token through every linear of one layer."""
    total = 0.0
    for _, din, dout in linear_sites(ms):
        if ms.linears == "dense":
            total += 2.0 * din * dout
        else:
            pl = bpmm_plan(din, dout, ms.max_block, ms.max_piece)
            total += 2.0 * pl["gin"] * pl["gout"] * _monarch_params(pl)
    return total


def bpmm_weight_bytes(ms: ModelShape, dbytes: int = 2) -> float:
    """Bytes of one layer's BPMM factors, read once per call."""
    total = 0.0
    for _, din, dout in linear_sites(ms):
        pl = bpmm_plan(din, dout, ms.max_block, ms.max_piece)
        total += pl["gin"] * pl["gout"] * _monarch_params(pl) * dbytes
    return total


def bpmm_io_bytes_per_token(ms: ModelShape, dbytes: int = 2) -> float:
    """Bytes one token moves through one layer's BPMM linears: its padded
    input slices read once and its output slices written once."""
    total = 0.0
    for _, din, dout in linear_sites(ms):
        pl = bpmm_plan(din, dout, ms.max_block, ms.max_piece)
        total += (pl["gin"] + pl["gout"]) * pl["piece"] * dbytes
    return total


def model_flops(ms: ModelShape, prompt: int, max_new: int, chunk: int) -> float:
    """Model FLOPs of serving one request: every prompt token and every
    decode token through the linears and pattern-live attention of every
    layer, and the LM head once per generated token (the last prompt
    token's logits give the first)."""
    tokens = prompt + max_new - 1
    calls, (dec_f, _) = request_attention(ms, prompt, max_new, chunk)
    attn = sum(f for f, _ in calls) + dec_f
    head = 2.0 * ms.d_model * ms.vocab * max_new
    return tokens * ms.layers * linear_flops_per_token(ms) + attn + head
