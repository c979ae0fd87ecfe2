"""Request objects, the priority admission queue, and small host-side
scheduling utilities shared by every serve engine (single-loop and
disaggregated)."""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import time

import jax.numpy as jnp
import numpy as np

__all__ = [
    "Request",
    "_PRIORITY_RANK",
    "_PagedSlot",
    "_AdmitQueue",
    "_AsyncTokens",
    "_next_bucket",
    "_to_device",
]


@dataclasses.dataclass
class _PagedSlot:
    """Host bookkeeping for one active request's pages: the retention
    schedule (from the block maps) plus its allocated tiles."""

    last_reader: np.ndarray  # (n_tiles,) last query position reading tile j
    peak_from: np.ndarray  # (L,) max future residency from frontier p
    length: int  # written-position horizon: plen + max_new - 1

    def remaining_peak(self, pos: int) -> int:
        return int(self.peak_from[min(pos, self.length - 1)])


# priority classes, best first.  Rank 0 is served ahead of rank 1 at every
# admission decision; the aging guard promotes a waiting batch request to
# rank 0 after ``aging_steps`` engine clocks so batch work is delayed under
# load, never starved.
_PRIORITY_RANK = {"interactive": 0, "batch": 1}


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray  # (S,) int32
    max_new: int
    arrival: int = 0  # engine steps: earliest step at which it exists
    priority: str = "interactive"  # scheduling class, see _PRIORITY_RANK
    generated: list[int] = dataclasses.field(default_factory=list)
    extras: dict = dataclasses.field(default_factory=dict)  # e.g. encdec frames
    # reset by each run(); SLO accounting in engine steps, not time:
    emit_clocks: list[int] = dataclasses.field(default_factory=list)
    ttft: int | None = None  # engine steps: first-token step minus arrival
    preemptions: int = 0  # times this request was evicted and requeued
    # seconds (time.perf_counter()): when the request first left the
    # admission queue; a resumed victim keeps its first stamp
    admitted: float | None = None


class _AdmitQueue:
    """Priority-ordered admission queue with an aging/starvation guard.

    ``peek(clock)`` returns the best ARRIVED request under the order
    (rank, arrival, insertion seq) — interactive ahead of batch, FIFO
    within a class — without removing it; the engine pops it only once its
    page reservation succeeds, so backpressure keeps the request queued.
    A batch request that has waited ``aging_steps`` clocks is promoted to
    the interactive rank (counted in ``promotions``): batch work is
    delayed under load, never starved.  ``fifo=True`` disables both the
    priority order and aging — the strict arrival-order baseline the
    --check-preempt gate compares against.  Preempted requests re-enter
    through ``push`` keeping their original ``arrival``, so their age (and
    any promotion) keeps accruing across evictions."""

    def __init__(self, requests: list[Request], aging_steps: int,
                 fifo: bool = False):
        self.aging_steps = aging_steps
        self.fifo = fifo
        self.promotions = 0
        self._seq = 0
        self._q: list[tuple[int, Request]] = []
        for r in requests:
            self.push(r)

    def __len__(self) -> int:
        return len(self._q)

    def push(self, r: Request) -> None:
        self._q.append((self._seq, r))
        self._seq += 1

    def rank(self, r: Request, clock: int) -> int:
        if self.fifo:
            return 0
        base = _PRIORITY_RANK[r.priority]
        if base and clock - r.arrival >= self.aging_steps:
            return 0  # aged: promoted to the interactive rank
        return base

    def peek(self, clock: int) -> Request | None:
        best_key, best = None, None
        for seq, r in self._q:
            if r.arrival > clock:
                continue
            key = (self.rank(r, clock), r.arrival, seq)
            if best_key is None or key < best_key:
                best_key, best = key, r
        return best

    def pop(self, r: Request, clock: int) -> None:
        for i, (_, q) in enumerate(self._q):
            if q is r:
                if (not self.fifo and _PRIORITY_RANK[r.priority]
                        and self.rank(r, clock) == 0):
                    self.promotions += 1
                if r.admitted is None:
                    r.admitted = time.perf_counter()
                del self._q[i]
                return
        raise ValueError(f"pop of request {r.uid} not in queue")


def _next_bucket(n: int, cap: int, floor: int = 8) -> int:
    """Smallest power-of-two >= n (>= floor), clamped at ``cap`` — the result
    is always a power of two or exactly ``cap``, so the jit shape cache stays
    bounded (at most log2(cap) values).  ``n`` must already be validated
    against ``cap`` (the engine checks prompts/positions against cache_len);
    a larger ``n`` is a caller bug, not a bucket to allocate."""
    if n > cap:
        raise ValueError(f"bucket request {n} exceeds cap {cap}")
    b = floor
    while b < n:
        b *= 2
    return min(b, cap)


def _to_device(host: np.ndarray):
    """A device copy of host scheduler state (positions, page and cross
    tables) that the engine mutates in place after dispatching a step.
    The copy is made on the host: the CPU backend aliases a 64-byte-aligned
    numpy buffer instead of copying it (``jnp.array(copy=True)`` included —
    its copy runs on the device, queued behind earlier steps), so a later
    ``pos[slot] += 1`` or a freed page-table entry would change the input of
    a step that is still queued."""
    return jnp.asarray(np.array(host))


class _AsyncTokens:
    """One-step-lag device-to-host token fetch.

    ``push(dev, sinks)`` registers a device array of sampled token ids and
    the (request, row) pairs that consumed them, starts an async copy, and
    resolves any record older than ``lag`` steps — so the host appends step
    t-1's values while step t's compute is already dispatched, and the
    per-token blocking ``np.asarray(argmax(...))`` sync disappears from the
    steady-state loop.  ``flush()`` resolves everything (end of run).

    ``phase`` is the engine's phase helper (``ServeLoop._phase``): each
    resolve, where the host blocks on the device, runs inside its
    ``"resolve"`` phase; without it resolves are untimed."""

    def __init__(self, lag: int = 1, phase=None):
        self.lag = lag
        self._phase = phase or (lambda name: contextlib.nullcontext())
        self._q: collections.deque = collections.deque()

    def push(self, dev, sinks: list[tuple[Request, int]]) -> None:
        dev.copy_to_host_async()
        self._q.append((dev, sinks))
        while len(self._q) > self.lag:
            self._resolve()

    def _resolve(self) -> None:
        with self._phase("resolve"):
            dev, sinks = self._q.popleft()
            vals = np.asarray(dev).reshape(-1)
            for r, i in sinks:
                r.generated.append(int(vals[i]))

    def flush(self) -> None:
        while self._q:
            self._resolve()
