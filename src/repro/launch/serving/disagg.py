"""Disaggregated prefill/decode serving: two phase-specialised workers over
ONE mesh-sharded page pool, coordinated by a host-side router.

The paper's multilayer-dataflow argument — pick the dataflow per phase
instead of forcing one loop shape onto both — applied at the serving layer:
prefill is a throughput phase (long chunked writes, wide attention reads),
decode is a latency phase (one token per request per step, shallow reads).
The single :class:`~repro.launch.serving.loop.ServeLoop` interleaves them in
one batch; here each phase gets its OWN slot bank:

* :class:`PrefillWorker` — ``prefill_batch`` slots that only stream prompt
  chunks (the ``(1, C)`` paged chunk entry point).  A slot that finishes its
  prompt samples the request's FIRST token and parks, waiting for handoff.
* :class:`DecodeWorker` — ``batch`` slots that only decode (the ``(B, 1)``
  paged decode wave).  Every active row advances every step by
  construction; prefill work can never stall it.
* :class:`DisaggRouter` — owns everything global: the admission queue, the
  :class:`~repro.launch.serving.pool.PagePool`, the radix prefix cache, the
  SLO clocks, and the preemption ladder.  It admits into the prefill
  worker, hands finished prefills to the decode worker, and preempts decode
  victims when a higher-priority admission cannot reserve.

**Handoff is ownership transfer, not data movement.**  Both workers read
the same device pools through per-slot page-table rows; the page table is
the transferable ownership record.  Moving a request from prefill slot
``s`` to decode slot ``d`` copies the table row (host ints), relabels each
page's pool reference from ``prefill:reqN`` to ``decode:reqN``
(:meth:`PagePool.transfer` — the refcount moves, it never duplicates or
drops), and seeds the decode feedback token with the first sampled token.
The KV rows themselves never move: on a ``pages``-sharded mesh they stay on
whichever shard allocated them, and both phases' kernels read them through
the (replicated) tables.

Rings (sliding-window) and encoder-decoder stacks are rejected: their page
sets are reused in phase / shared read-only, which makes them
non-preemptible in the single loop and non-transferable here — the single
loop remains the right engine for those families, and for any deployment
where one batch is enough to keep both phases busy."""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.launch.serving.loop import ServeLoop
from repro.launch.serving.queueing import (
    Request,
    _AdmitQueue,
    _AsyncTokens,
    _PagedSlot,
    _PRIORITY_RANK,
    _next_bucket,
    _to_device,
)

__all__ = ["PrefillWorker", "DecodeWorker", "DisaggRouter"]


@dataclasses.dataclass
class _Handoff:
    """A finished prefill waiting for a decode slot: the request, its
    retention schedule, its next write position, and the first sampled
    token (a device scalar — the host never syncs on it)."""

    r: Request
    sched: _PagedSlot
    pos: int
    tok1: object  # device scalar int32


class PrefillWorker:
    """Slot bank of the prefill phase: per-slot host state for requests
    mid-prompt.  The router mutates it; the worker only owns the layout."""

    def __init__(self, n_slots: int, n_vtiles: int, sentinel: int):
        self.n_slots = n_slots
        self.active: list[Request | None] = [None] * n_slots
        self.sched: list[_PagedSlot | None] = [None] * n_slots
        self.parr: list[np.ndarray | None] = [None] * n_slots
        self.pos = np.zeros(n_slots, np.int32)
        self.consumed = np.zeros(n_slots, np.int32)
        self.owed = np.zeros(n_slots, np.int32)  # decode tokens at admission
        self.pt = np.full((n_slots, n_vtiles), sentinel, np.int32)
        self.done: list[_Handoff | None] = [None] * n_slots
        self.rr = 0  # round-robin offset of the chunk budget

    def busy(self) -> bool:
        return any(r is not None for r in self.active)

    def free_slots(self) -> list[int]:
        return [s for s in range(self.n_slots) if self.active[s] is None]


class DecodeWorker:
    """Slot bank of the decode phase: every active row decodes one token per
    step.  Rows arrive only through handoff (the router fills them)."""

    def __init__(self, n_slots: int, n_vtiles: int, sentinel: int):
        self.n_slots = n_slots
        self.active: list[Request | None] = [None] * n_slots
        self.sched: list[_PagedSlot | None] = [None] * n_slots
        self.parr: list[np.ndarray | None] = [None] * n_slots
        self.pos = np.zeros(n_slots, np.int32)
        self.remaining = np.zeros(n_slots, np.int32)
        self.admit_pos = np.zeros(n_slots, np.int32)  # preemption floor
        self.admit_seq = np.zeros(n_slots, np.int64)  # victim tiebreak
        self.pt = np.full((n_slots, n_vtiles), sentinel, np.int32)
        self.nxt = jnp.zeros((n_slots,), jnp.int32)

    def busy(self) -> bool:
        return any(r is not None for r in self.active)

    def free_slots(self) -> list[int]:
        return [s for s in range(self.n_slots) if self.active[s] is None]


class DisaggRouter(ServeLoop):
    """Phase-disaggregated paged serve engine.

    Subclasses :class:`ServeLoop` for everything global — pool, radix tree,
    schedules, reservation discipline, preemption ladder, SLO accounting —
    and replaces the single interleaved loop with a prefill worker, a decode
    worker, and a handoff step between them.  ``batch`` sizes the DECODE
    worker (it is the concurrency limit that matters for ITL);
    ``prefill_batch`` sizes the prefill worker.  Greedy decoding through
    the same compiled entry points keeps the emitted tokens identical to
    the single loop's — the --check-shard gate pins that parity.

    Preemption only ever evicts DECODE rows: a prefill row's pages are
    donated back to the radix tree at eviction anyway, so evicting
    mid-prefill work saves nothing over letting it finish, while evicting a
    decode row frees its whole resident set.  Victims requeue through the
    router's admission path and re-prefill (warm via the radix tree) in the
    prefill worker at the satellite reduced budget share."""

    def __init__(self, cfg, mesh, params, *, batch: int,
                 prefill_batch: int = 1, **kw):
        if prefill_batch < 1:
            raise ValueError(
                f"prefill_batch must be >= 1, got {prefill_batch}"
            )
        if cfg.sliding_window:
            raise ValueError(
                "disaggregated serving does not support sliding-window "
                "rings: a ring's fixed in-phase page set spans prefill and "
                "decode, so there is no ownership to hand off — use the "
                "single-loop engine"
            )
        if cfg.family == "encdec":
            raise ValueError(
                "disaggregated serving does not support encoder-decoder "
                "stacks: the shared read-only cross ranges make requests "
                "non-preemptible and tie admission to the encoder cache — "
                "use the single-loop engine"
            )
        kw.setdefault("paged", True)
        kw.setdefault("chunked", True)
        if not (kw["paged"] and kw["chunked"]):
            raise ValueError(
                "disaggregated serving is paged+chunked by construction"
            )
        super().__init__(cfg, mesh, params, batch=batch, **kw)
        self.prefill_batch = prefill_batch

    def _slot_owner(self, r: Request) -> str:
        # preemption only ever evicts decode-phase rows
        return f"decode:req{r.uid}"

    def run(self, requests: list[Request]) -> list[Request]:
        self._validate(requests)
        return self._run_disagg(requests)

    # -- the router loop --------------------------------------------------

    def _commit_all(self, pw: PrefillWorker, dw: DecodeWorker) -> int:
        """Both workers' committed worst-case future residency — admission
        reserves against the union, so handoff never needs pages."""
        return (self._committed(pw.active, pw.sched, pw.pos)
                + self._committed(dw.active, dw.sched, dw.pos))

    def _run_disagg(self, requests: list[Request]) -> list[Request]:
        C = self.chunk_size
        q = _AdmitQueue(requests, self.aging_steps, self.fifo)
        pw = PrefillWorker(self.prefill_batch, self.n_vtiles, self.pool_pages)
        dw = DecodeWorker(self.batch, self.n_vtiles, self.pool_pages)
        pool = self.pool
        fetch = _AsyncTokens(lag=1)
        aseq = 0
        self.stats = {
            "prefill_calls": 0, "mixed_steps": 0, "chunk_calls": 0,
            "decode_steps": 0, "prefill_tokens": 0, "decode_tokens": 0,
            "decode_stall_steps": 0, "overlap_steps": 0,
            "admission_backpressure": 0, "max_concurrent": 0,
            "prefill_flops": 0.0, "prefix_hits": 0, "prefix_hit_tokens": 0,
            "preemptions": 0, "resumes": 0, "resume_warm_hits": 0,
            "handoffs": 0, "handoff_wait_steps": 0,
            "prefill_batch": self.prefill_batch, "decode_batch": self.batch,
        }
        clock = 0
        with self.mesh:
            caches = (
                self._pools if self._pools is not None else self._zero_pools()
            )
            while (len(q) or pw.busy() or dw.busy()):
                # -- admission into the PREFILL worker --------------------
                for slot in pw.free_slots():
                    r = q.peek(clock)
                    if r is None:
                        break
                    pr = self._eff_prompt(r)
                    owed = r.max_new - len(r.generated)
                    L = len(pr) + owed - 1
                    own = f"prefill:req{r.uid}"
                    rank = _PRIORITY_RANK[r.priority]
                    m, spages = self._match_prefix(pr)
                    if m:
                        for p in spages:
                            pool.retain(p, owner=own)
                        sc = self._paged_schedule(
                            L, step_span=C, start_tile=m // self.page
                        )
                        need = lambda: (
                            self._commit_all(pw, dw) + sc.remaining_peak(m)
                        )
                        gap = self._fits(need())
                        if gap > 0 and self.preemptible:
                            gap = self._preempt_until(
                                need, rank, q, fetch, pool, dw.pt,
                                dw.active, dw.sched, dw.parr, dw.pos,
                                dw.admit_pos, dw.admit_seq,
                            )
                        if gap > 0:
                            for p in spages:
                                pool.release(p, owner=own)
                            cold_peak = self._paged_schedule(
                                L, step_span=C
                            ).remaining_peak(0)
                            if cold_peak < sc.remaining_peak(m):
                                m, spages = 0, []
                            else:
                                self.stats["admission_backpressure"] += 1
                                break
                    if not m:
                        sc = self._paged_schedule(L, step_span=C)
                        need = lambda: (
                            self._commit_all(pw, dw) + sc.remaining_peak(0)
                        )
                        gap = self._fits(need())
                        if gap > 0 and self.preemptible:
                            gap = self._preempt_until(
                                need, rank, q, fetch, pool, dw.pt,
                                dw.active, dw.sched, dw.parr, dw.pos,
                                dw.admit_pos, dw.admit_seq,
                            )
                        if gap > 0:
                            self.stats["admission_backpressure"] += 1
                            break
                    q.pop(r, clock)
                    if r.preemptions:
                        self.stats["resumes"] += 1
                        if m:
                            self.stats["resume_warm_hits"] += 1
                    if m:
                        for i, p in enumerate(spages):
                            pw.pt[slot, i] = p
                        self.stats["prefix_hits"] += 1
                        self.stats["prefix_hit_tokens"] += m
                    pw.active[slot] = r
                    pw.sched[slot] = sc
                    pw.parr[slot] = pr
                    pw.pos[slot] = m
                    pw.consumed[slot] = m
                    pw.owed[slot] = owed
                self.stats["max_concurrent"] = max(
                    self.stats["max_concurrent"],
                    sum(a is not None for a in pw.active)
                    + sum(a is not None for a in dw.active),
                )
                # -- handoff: finished prefills -> free decode slots ------
                waiting = [s for s in range(pw.n_slots) if pw.done[s]]
                if waiting:
                    frees = dw.free_slots()
                    for s, d in zip(waiting, frees):
                        h = pw.done[s]
                        r = h.r
                        dw.pt[d, :] = pw.pt[s, :]
                        pw.pt[s, :] = self.pool_pages
                        for t in range(dw.pt.shape[1]):
                            pid = int(dw.pt[d, t])
                            if pid != self.pool_pages:
                                pool.transfer(
                                    pid, f"prefill:req{r.uid}",
                                    f"decode:req{r.uid}",
                                )
                        dw.active[d] = r
                        dw.sched[d] = h.sched
                        dw.parr[d] = pw.parr[s]
                        dw.pos[d] = h.pos
                        dw.remaining[d] = pw.owed[s] - 1  # tok1 already out
                        dw.admit_pos[d] = h.pos
                        dw.admit_seq[d] = aseq
                        aseq += 1
                        dw.nxt = dw.nxt.at[d].set(h.tok1)
                        pw.done[s] = None
                        pw.active[s] = None
                        pw.sched[s] = None
                        pw.parr[s] = None
                        self.stats["handoffs"] += 1
                    if len(waiting) > len(frees):
                        # decode full: the parked prefill slots backpressure
                        # the prefill worker until a decode row retires
                        self.stats["handoff_wait_steps"] += 1
                if not (pw.busy() or dw.busy()):
                    clock += 1  # idle tick: waiting on arrivals
                    continue
                clock += 1
                self.stats["mixed_steps"] += 1
                # -- decode wave (every active decode row, every step) ----
                dec_rows = [
                    d for d in range(dw.n_slots) if dw.active[d] is not None
                ]
                if dec_rows:
                    for d in dec_rows:
                        caches = self._ensure_writable(
                            pool, dw.pt, d, int(dw.pos[d]),
                            int(dw.pos[d]) + 1, caches,
                            f"decode:req{dw.active[d].uid}",
                        )
                    hot = max(int(dw.pos[d]) + 1 for d in dec_rows)
                    kv_live = _next_bucket(hot, self.cache_len)
                    self.stats["decode_kv_live_max"] = max(
                        self.stats.get("decode_kv_live_max", 0), kv_live
                    )
                    use = np.asarray(
                        [a is not None for a in dw.active], bool
                    )
                    pt_wave = np.where(
                        use[:, None], dw.pt, np.int32(self.pool_pages)
                    ).astype(np.int32)
                    logits, caches = self.p_decode_fn(
                        self.params, caches, dw.nxt[:, None],
                        _to_device(dw.pos), jnp.asarray(pt_wave), kv_live,
                    )
                    toks = jnp.argmax(logits, -1).astype(jnp.int32)
                    self.stats["decode_steps"] += 1
                    self.stats["decode_tokens"] += len(dec_rows)
                    sinks = []
                    for d in dec_rows:
                        r = dw.active[d]
                        sinks.append((r, d))
                        dw.pos[d] += 1
                        dw.remaining[d] -= 1
                        if dw.remaining[d] <= 0:
                            self._free_all(
                                pool, dw.pt, d, f"decode:req{r.uid}"
                            )
                            dw.active[d] = None
                            dw.sched[d] = None
                            dw.parr[d] = None
                        else:
                            self._free_dead(
                                pool, dw.pt, d, dw.sched[d],
                                int(dw.pos[d]), f"decode:req{r.uid}",
                            )
                    self._stamp_emits(sinks, clock)
                    fetch.push(toks, sinks)
                    dw.nxt = jnp.where(jnp.asarray(use), toks, dw.nxt)
                # -- prefill chunks under the step budget -----------------
                budget = self.chunk_budget
                order = sorted(
                    range(pw.n_slots),
                    key=lambda s: (
                        0 if self.fifo or pw.active[s] is None
                        else _PRIORITY_RANK[pw.active[s].priority],
                        (s - pw.rr) % pw.n_slots,
                    ),
                )
                pw.rr = (pw.rr + 1) % pw.n_slots
                did_chunk = False
                for slot in order:
                    r = pw.active[slot]
                    if r is None or pw.done[slot] is not None:
                        continue  # empty, or parked awaiting handoff
                    rem_prompt = len(pw.parr[slot]) - pw.consumed[slot]
                    t = self._budget_draw(r, rem_prompt, budget)
                    if t <= 0:
                        continue
                    budget -= t
                    own = f"prefill:req{r.uid}"
                    caches = self._ensure_writable(
                        pool, pw.pt, slot, int(pw.pos[slot]),
                        int(pw.pos[slot]) + t, caches, own,
                    )
                    ctoks = np.zeros((1, C), np.int32)
                    ctoks[0, :t] = pw.parr[slot][
                        pw.consumed[slot] : pw.consumed[slot] + t
                    ]
                    kv_live = _next_bucket(
                        int(pw.pos[slot]) + t, self.cache_len
                    )
                    logits1, caches = self.p_chunk_fn(
                        self.params, caches, jnp.asarray(ctoks),
                        _to_device(pw.pt[slot : slot + 1]),
                        jnp.int32(pw.pos[slot]), jnp.int32(t), kv_live,
                    )
                    did_chunk = True
                    self.stats["chunk_calls"] += 1
                    self.stats["prefill_tokens"] += t
                    self.stats["prefill_flops"] += self._prefill_flop_count(
                        int(pw.pos[slot]), t
                    )
                    pw.pos[slot] += t
                    pw.consumed[slot] += t
                    if pw.consumed[slot] == len(pw.parr[slot]):
                        self._cache_pages(pw.parr[slot], pw.pt, slot)
                        tok1 = jnp.argmax(logits1).astype(jnp.int32)
                        self._stamp_emits([(r, 0)], clock)
                        fetch.push(tok1, [(r, 0)])
                        if pw.owed[slot] <= 1:
                            # max_new == 1: the prefill token was the whole
                            # response — retire without a handoff
                            self._free_all(pool, pw.pt, slot, own)
                            pw.active[slot] = None
                            pw.sched[slot] = None
                            pw.parr[slot] = None
                            continue
                        pw.done[slot] = _Handoff(
                            r=r, sched=pw.sched[slot],
                            pos=int(pw.pos[slot]), tok1=tok1,
                        )
                    self._free_dead(pool, pw.pt, slot, pw.sched[slot],
                                    int(pw.pos[slot]), own)
                if dec_rows and did_chunk:
                    self.stats["overlap_steps"] += 1
        fetch.flush()
        self._pools = caches
        self._finish_paged_run(pool)
        self._finalize_slo(requests, q)
        return requests
