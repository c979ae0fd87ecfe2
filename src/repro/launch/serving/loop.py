"""ServeLoop: the single-process serving engine — static, admission,
chunked, and paged continuous batching over one page pool.

The pool may be host- AND mesh-sharded: ``page_shards > 1`` splits the
physical page range into contiguous per-shard sub-pools (balanced
allocation in :class:`repro.launch.serving.pool.PagePool`), and on a mesh
with a ``pages`` axis the device-side pools shard over the same ranges
(see :func:`repro.models.transformer.paged_pool_specs`).  The
disaggregated prefill/decode engine
(:class:`repro.launch.serving.disagg.DisaggRouter`) subclasses this loop
and reuses its schedule/reservation/preemption machinery."""

from __future__ import annotations

import collections
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import quant, sparsity
from repro.core.attention import override_attention
from repro.distributed import sharding as shd
from repro.models import model as M
from repro.models import transformer as tf
from repro.models.config import ModelConfig

from repro.launch.serving.entries import (
    abstract_cache,
    cache_shardings,
    make_mixed_fn,
    make_paged_fns,
    make_serve_fns,
    make_slot_chunk_fn,
    zero_pools,
)
from repro.launch.serving.pool import PagePool, RadixCache
from repro.launch.serving.queueing import (
    Request,
    _AdmitQueue,
    _AsyncTokens,
    _PagedSlot,
    _PRIORITY_RANK,
    _next_bucket,
    _to_device,
)

__all__ = ["ServeLoop"]

# host phases of the paged chunked engine: profiler span, self-time counter
# and longest-self-time counter (none for resolve, where the host waits on
# the device rather than working)
_PHASES = {
    name: (f"serve.{name}", f"host_{name}_s",
           None if name == "resolve" else f"host_{name}_max_s")
    for name in ("step", "admit", "decode", "chunk", "resolve")
}


class _Phase:
    """One use of :meth:`ServeLoop._phase`: a profiler span around the
    phase, and its host self time (its length less its child phases') added
    to the loop's counters."""

    __slots__ = ("loop", "keys", "ann", "t0")

    def __init__(self, loop: "ServeLoop", name: str, step: int | None, kw):
        self.loop, self.keys = loop, _PHASES[name]
        span = self.keys[0]
        self.ann = (
            jax.profiler.StepTraceAnnotation(span, step_num=step, **kw)
            if step is not None else jax.profiler.TraceAnnotation(span, **kw)
        )

    def __enter__(self) -> None:
        self.loop._phase_open.append(0.0)
        self.ann.__enter__()
        self.t0 = time.perf_counter()

    def __exit__(self, *exc) -> bool:
        dt = time.perf_counter() - self.t0
        self.ann.__exit__(*exc)
        open_ = self.loop._phase_open
        own = dt - open_.pop()
        if open_:
            open_[-1] += dt  # the parent's child time
        stats, (_, total, longest) = self.loop.stats, self.keys
        stats[total] += own
        if longest is not None and own > stats[longest]:
            stats[longest] = own
        return False


class ServeLoop:
    """Streaming serve engine (greedy sampling), two scheduling modes.

    **Chunked** — mixed-step scheduling: every iteration advances all slots
    through the ONE unified entry point (``tf.mixed_step``) at two ragged
    shapes — a (B, 1) decode wave (all decoding rows sample one token,
    kv_live bucketed at *their* live depth) plus a (1, C) slot-chunk call
    per mid-prompt row (up to ``chunk_size`` prompt tokens written straight
    into the slot's rows of the shared cache, bucketed at the prompt's own
    frontier).  Admission costs nothing (a freed slot just starts consuming
    the next request's chunks), a per-step ``chunk_budget`` caps total
    prefill tokens per iteration so decode latency stays bounded, and
    ``kv_live`` buckets (powers of two) bound the compiled shape count.
    Decode rows advance on EVERY step by construction —
    ``stats["decode_stall_steps"]`` stays 0.

    **Admission-prefill** (``chunked=False``) — the slot admit/evict engine:
    each admission runs a bucketed batch-1 prefill and inserts the caches at
    the slot index; all live decode slots idle for that prefill
    (``stats["admission_stall_steps"]`` counts them).  This is the seed
    contiguous engine, kept as the parity baseline; with
    ``static_batching=True`` it degrades admission to wave scheduling (the
    serve_throughput baseline).

    Both modes fetch sampled tokens with a one-step lag (`_AsyncTokens`):
    the decode feedback token stays on device, the host only tracks counts
    (stopping is length-based), so the loop never blocks on the current
    step's values.

    Per-slot host state mirrors the device-side (B,)-vector threading:
    ``pos[b]`` is request b's next write position (== tokens seen so far),
    so RoPE angles, cache writes and live-KV masks are all per-request.
    Prompts are *right*-padded / chunk-aligned — real tokens at positions
    0..L-1, positions and causal masks exact, pad keys never attended.

    ``paged=True`` additionally runs a radix-tree **prefix cache**
    (``prefix_cache=False`` disables it): completed prompts donate their
    full KV pages to a :class:`RadixCache`, admission longest-prefix
    matches new prompts against it, and a hit aliases the matched physical
    pages into the request's page table — prefill then starts at the
    divergence frontier and the admission reservation covers only the
    unique suffix.  Shared pages are refcounted in the :class:`PagePool`
    and copy-on-write forked before any divergent write.

    The page table is the ONLY cache substrate beyond the contiguous
    baseline: a **sliding-window** config serves through a mod-window ring
    table (``ring_tiles`` slots reused in phase, unbounded decode length,
    a fixed page set held per request) and an **encoder-decoder** config
    serves through read-only shared cross page ranges (the encoder output
    prefills once per distinct ``frames`` input; repeat inputs alias the
    cached range, counted as ``prefix_hits``; decode never writes cross
    pages so copy-on-write never triggers).  ``chunked=True`` requests for
    either family upgrade to ``paged=True`` automatically.  The token
    radix tree is disabled for those two families (ring slots are reused
    in phase; encdec decoder KV depends on the frames through
    cross-attention) — the encoder cache is their sharing layer.

    The :class:`PagePool`, the radix tree, and the encoder cache PERSIST
    across ``run()`` calls — a warm second run hits the first run's
    prefixes.  Call :meth:`close` to release the engine-held references;
    it raises if the pools do not drain to zero.
    """

    def __init__(
        self, cfg: ModelConfig, mesh: Mesh, params, *,
        batch: int, cache_len: int, attn_impl: str | None = None,
        attn_pattern: str | None = None, static_batching: bool = False,
        chunked: bool = False, chunk_size: int = 32,
        chunk_budget: int | None = None, paged: bool = False,
        page: int | None = None, pool_pages: int | None = None,
        page_shards: int | None = None, prefix_cache: bool = True,
        scheduler: str = "priority", aging_steps: int = 64,
        max_preemptions: int = 2, preempt_min_progress: int = 1,
        resume_chunk_frac: float = 0.5, slo_ttft: int | None = None,
        slo_itl: float | None = None, kv_dtype: str = "bf16",
    ):
        cfg = override_attention(cfg, impl=attn_impl, pattern=attn_pattern)
        quant.validate_kv_dtype(kv_dtype)
        if kv_dtype != "bf16" and not paged:
            raise ValueError(
                "kv_dtype quantization is a paged-pool feature (scales ride "
                "the page tables) — pass paged=True or kv_dtype='bf16'"
            )
        if cfg.sliding_window and cache_len < cfg.sliding_window:
            raise ValueError(
                f"cache_len {cache_len} < sliding_window {cfg.sliding_window}: "
                "the ring modulus must equal the window for prefill/decode "
                "phase alignment"
            )
        stateful = [s.mixer for s in cfg.period_slots if s.mixer != "attn"]
        if stateful:
            raise ValueError(
                f"{cfg.name}: ragged serving requires attention-only stacks — "
                f"{stateful} mixers integrate right-pad tokens into their "
                "state during bucketed prefill (no per-row mask can undo it)"
            )
        if chunked:
            if static_batching:
                raise ValueError("chunked and static_batching are exclusive: "
                                 "chunked scheduling IS continuous")
            if chunk_size < 1:
                raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
            if chunk_budget is not None and chunk_budget < 1:
                raise ValueError(
                    f"chunk_budget must be >= 1, got {chunk_budget} — a "
                    "zero budget would starve prefill rows forever"
                )
        if paged and static_batching:
            raise ValueError("paged and static_batching are exclusive")
        if (chunked or paged) and cfg.n_img_tokens:
            # the ONE remaining extras rejection: stub image-patch tokens are
            # prepended inside prefill and have no chunk/page write path yet
            raise ValueError(
                "image-token extras have no chunked/paged path; use the "
                "admission-prefill engine (chunked=False, paged=False)"
            )
        if chunked and not paged and (
            cfg.sliding_window or cfg.family == "encdec"
        ):
            # one cache substrate: a chunked request for a ring or encoder-
            # decoder cache upgrades to the paged engine — the mod-window /
            # read-only page tables ARE the streaming layout for these
            # families (there is no contiguous chunked ring/encdec path)
            paged = True
        if scheduler not in ("priority", "fifo"):
            raise ValueError(
                f"scheduler must be 'priority' or 'fifo', got {scheduler!r}"
            )
        if aging_steps < 1:
            raise ValueError(f"aging_steps must be >= 1, got {aging_steps}")
        if max_preemptions < 0:
            raise ValueError(
                f"max_preemptions must be >= 0, got {max_preemptions}"
            )
        if preempt_min_progress < 1:
            raise ValueError(
                "preempt_min_progress must be >= 1, got "
                f"{preempt_min_progress} — zero progress between evictions "
                "is a livelock"
            )
        if not 0.0 < resume_chunk_frac <= 1.0:
            raise ValueError(
                f"resume_chunk_frac must be in (0, 1], got {resume_chunk_frac}"
            )
        self.cfg, self.mesh, self.params = cfg, mesh, params
        self.batch, self.cache_len = batch, cache_len
        self.static_batching = static_batching
        self.chunked = chunked
        self.chunk_size = chunk_size
        self.chunk_budget = chunk_budget if chunk_budget is not None else chunk_size
        self.fifo = scheduler == "fifo"
        self.aging_steps = aging_steps
        self.max_preemptions = max_preemptions
        self.preempt_min_progress = preempt_min_progress
        self.resume_chunk_frac = resume_chunk_frac
        self.slo_ttft = slo_ttft
        self.slo_itl = slo_itl
        self._closed = False
        self._phase_open: list[float] = []  # child time of each open phase
        # preemption needs a page substrate to evict from and a restartable
        # resume path; rings hold fixed in-phase page sets and encdec KV
        # depends on the frames through cross-attention — both families are
        # NON-preemptible (nothing warm to resume from, by declaration)
        self.preemptible = (
            paged and not self.fifo and max_preemptions > 0
            and not cfg.sliding_window and cfg.family != "encdec"
        )
        self.paged = paged
        self.kv_dtype = kv_dtype
        if paged:
            spec = cfg.attention_spec
            # one page == one kv tile of the effective grid, so the packed
            # live tables ARE the page-table domain (tile-granular paging)
            self.page = page if page is not None else sparsity.pick_pattern_tiles(
                1, cache_len, spec.q_tile, spec.kv_tile
            )[1]
            if self.page < 1:
                raise ValueError(f"page must be >= 1 token, got {self.page}")
            self.ring_tiles: int | None = None
            if cfg.sliding_window:
                # mod-window ring: the table has exactly ring_tiles slots and
                # absolute tile j lives in slot j % ring_tiles — a window-
                # sized page set reused in phase, positions unbounded
                self.ring_tiles = sparsity.ring_tiles_for(
                    cfg.sliding_window, chunk_size, self.page
                )
                self.n_vtiles = self.ring_tiles
            else:
                self.n_vtiles = -(-cache_len // self.page)
            # default pool budget == the dense reservation the contiguous
            # engine would make (batch x cache_len rows; batch rings for a
            # window config) — benchmarks shrink it to show the capacity win
            self.pool_pages = (
                pool_pages if pool_pages is not None else batch * self.n_vtiles
            )
            if self.pool_pages < 1:
                raise ValueError(
                    f"pool_pages must be >= 1, got {self.pool_pages}"
                )
            # host-side page sharding mirrors the mesh: a "pages" axis splits
            # the pool's physical range into contiguous per-device sub-pools
            # (GSPMD partitions the page rows the same way), so the host
            # allocator's shard ranges ARE the device placement.  Explicit
            # page_shards overrides (host-only sharding on a 1-device mesh is
            # how the capacity accounting is tested without real devices).
            if page_shards is None:
                axes = dict(zip(mesh.axis_names, mesh.devices.shape))
                page_shards = axes.get("pages", 1)
            if page_shards < 1:
                raise ValueError(
                    f"page_shards must be >= 1, got {page_shards}"
                )
            self.page_shards = page_shards
            if self.pool_pages % page_shards:
                # round UP to a shard multiple — never shrink a user budget
                self.pool_pages += page_shards - self.pool_pages % page_shards
            # encoder-decoder: a SEPARATE read-only cross pool — encoder
            # outputs prefill once, decoders alias; sized for one distinct
            # encoder input per slot (the frames cache shares below that)
            self.cross_pages: int | None = None
            if cfg.family == "encdec":
                self.cross_tiles = -(-cfg.enc_seq // self.page)
                self.cross_pages = batch * self.cross_tiles
                self.cross_pool = PagePool(self.cross_pages)
                self._cross_cache: collections.OrderedDict[
                    str, list[int]
                ] = collections.OrderedDict()
            # prefix sharing: the radix tree is token-keyed, so it is OFF for
            # rings (slots are reused in phase — nothing stable to alias) and
            # for encdec decoders (self-KV depends on the encoder output
            # through cross-attention, not on tokens alone); encdec gets the
            # frames-keyed encoder cache instead.  Both the tree and the page
            # pool PERSIST across run() calls — drain checks live in close().
            self.prefix_cache = (
                prefix_cache and not cfg.sliding_window
                and cfg.family != "encdec"
            )
            self.pool = PagePool(self.pool_pages, n_shards=self.page_shards)
            self.radix: RadixCache | None = (
                RadixCache(self.pool, self.page) if self.prefix_cache else None
            )
            self._pools = None  # device pools, lazily built, persist too
            self._sched_cache: dict[tuple, _PagedSlot] = {}
            (self.p_prefill_fn, self.p_decode_fn, self.p_chunk_fn,
             self.p_copy_fn, self.p_encode_fn) = make_paged_fns(
                cfg, mesh, n_pages=self.pool_pages, page=self.page,
                chunk=chunk_size, cross_pages=self.cross_pages,
                kv_dtype=kv_dtype,
            )
            self.stats = {}
            return
        if chunked:
            # ONE entry point (tf.mixed_step), two ragged shapes: the (B, 1)
            # decode wave advances every decoding row each iteration at the
            # decode rows' OWN kv_live bucket, and each (1, C) slot-chunk
            # call streams a prompt chunk into the shared cache at its own
            # frontier bucket — decode work and prefill work never inflate
            # each other's compiled shapes or compute
            self.mixed1_fn = make_mixed_fn(
                cfg, mesh, batch=batch, cache_len=cache_len, chunk=1
            )
            self.chunk_fn = make_slot_chunk_fn(
                cfg, mesh, batch=batch, cache_len=cache_len, chunk=chunk_size
            )
        else:
            # batch-1 ragged prefill (jit retraces per bucket shape; caches
            # insert at a traced slot index so one compile covers every slot)
            # + batch-wide ragged decode, through the sharded entry points
            self.prefill_fn, _ = make_serve_fns(
                cfg, mesh, batch=1, cache_len=cache_len, ragged=True
            )
            _, self.decode_fn = make_serve_fns(
                cfg, mesh, batch=batch, cache_len=cache_len, ragged=True
            )
            self._insert = jax.jit(
                lambda caches, wave, slot: jax.tree.map(
                    lambda c, w: jax.lax.dynamic_update_slice_in_dim(
                        c, w.astype(c.dtype), slot, axis=1
                    ),
                    caches,
                    wave,
                ),
                donate_argnums=(0,),
            )
        self.stats: dict[str, int] = {}

    # -- per-slot prefill (admission-prefill mode) ------------------------

    def _prefill_one(self, r: Request):
        """Prefill one request (batch=1, right-padded to a bucket); returns
        (first sampled token — a DEVICE scalar, batch-1 cache tree)."""
        ln = len(r.prompt)
        bucket = _next_bucket(ln, self.cache_len)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :ln] = r.prompt
        b = {"tokens": jnp.asarray(toks)}
        for key, val in r.extras.items():
            b[key] = jnp.asarray(val)[None]
        logits, wave = self.prefill_fn(self.params, b, jnp.asarray([ln], jnp.int32))
        self.stats["prefill_calls"] = self.stats.get("prefill_calls", 0) + 1
        return jnp.argmax(logits[0]).astype(jnp.int32), wave

    def _zero_caches(self):
        specs = tf.cache_specs(self.cfg, self.batch, self.cache_len)
        dt = jnp.dtype(self.cfg.dtype)
        return jax.tree.map(
            lambda s: jnp.zeros(s.shape, dt),
            specs,
            is_leaf=lambda x: isinstance(x, shd.ParamSpec),
        )

    def _validate(self, requests: list[Request]) -> None:
        for r in requests:
            if r.arrival < 0:
                raise ValueError(
                    f"request {r.uid}: negative arrival {r.arrival} — the "
                    "engine clock starts at 0"
                )
            if r.priority not in _PRIORITY_RANK:
                raise ValueError(
                    f"request {r.uid}: unknown priority {r.priority!r} "
                    f"(expected one of {sorted(_PRIORITY_RANK)})"
                )
            if len(r.prompt) < 1:
                raise ValueError(f"request {r.uid}: prompt must be non-empty")
            if len(r.prompt) > self.cache_len:
                raise ValueError(
                    f"request {r.uid}: prompt {len(r.prompt)} > cache_len {self.cache_len}"
                )
            if r.max_new < 1:
                raise ValueError(f"request {r.uid}: max_new must be >= 1")
            # without a ring, decode writes positions L .. L+max_new-2 straight
            # into the cache — past cache_len they would silently clamp
            need = len(r.prompt) + r.max_new - 1
            if not self.cfg.sliding_window and need > self.cache_len:
                raise ValueError(
                    f"request {r.uid}: prompt+max_new needs {need} cache rows "
                    f"> cache_len {self.cache_len}"
                )
            if self.paged:
                if self.ring_tiles is not None:
                    # a ring request holds a FIXED page set to retirement
                    peak = min(self.ring_tiles, -(-need // self.page))
                elif self.chunked or self.cfg.family == "encdec":
                    # encdec admission streams the decoder prompt through
                    # the chunk entry point, so its spans are chunk-sized
                    peak = self._paged_schedule(
                        need, self.chunk_size
                    ).remaining_peak(0)
                else:
                    peak = self._paged_schedule(
                        need, len(r.prompt)
                    ).remaining_peak(0)
                if peak > self.pool_pages:
                    raise ValueError(
                        f"request {r.uid}: needs {peak} resident pages at its "
                        f"peak > pool of {self.pool_pages} — unservable at "
                        "this page budget"
                    )
                if self.cross_pages is not None and "frames" not in r.extras:
                    raise ValueError(
                        f"request {r.uid}: encoder-decoder serving needs "
                        "'frames' extras (the encoder input)"
                    )
            r.generated.clear()
            r.emit_clocks.clear()
            r.ttft = None
            r.admitted = None
            r.preemptions = 0

    def _phase(self, name: str, step: int | None = None, **kw) -> _Phase:
        """Context manager for one host phase of the paged chunked engine
        (a name of ``_PHASES``): a ``serve.<name>`` profiler span carrying
        ``kw`` (a step span numbered ``step`` when given), and the phase's
        self time in ``stats["host_<name>_s"]`` and, but for resolve, its
        longest single self time in ``stats["host_<name>_max_s"]``.  Always
        on: with the profiler off a phase costs a few microseconds."""
        return _Phase(self, name, step, kw)

    # -- engine loops -----------------------------------------------------

    def run(self, requests: list[Request]) -> list[Request]:
        """Serve every request to completion; returns them in input order."""
        self._validate(requests)
        if self.paged:
            if self.chunked:
                return self._run_paged_chunked(requests)
            return self._run_paged_admission(requests)
        if self.chunked:
            return self._run_chunked(requests)
        return self._run_admission(requests)

    # -- paged engine: page pool + per-request tile-granular page tables ----

    def _zero_pools(self):
        # device_put at the MESH shardings (entries.zero_pools): on a mesh
        # with a "pages" axis the page rows land sharded before the first
        # donated entry-point call, instead of committing replicated and
        # resharding on entry
        return zero_pools(
            self.cfg, self.mesh, self.pool_pages, self.page,
            cross_pages=self.cross_pages, kv_dtype=self.kv_dtype,
        )

    def _paged_schedule(
        self, length: int, step_span: int, start_tile: int = 0
    ) -> _PagedSlot:
        """Retention schedule for one request whose written positions span
        ``0..length-1``: per-tile last-reader positions (the union over every
        attention slot's pattern — one page table serves all layers) and the
        max-future-residency curve that backs the reservation discipline.
        ``step_span`` is the engine's largest single advance (chunk size, or
        the whole prompt for a monolithic admission prefill) — tiles
        allocated mid-step widen residency by that much.  ``start_tile > 0``
        prices only the unique suffix of a prefix-cache hit: aliased tiles
        are carried by the radix cache's references, the request allocates
        nothing below its divergence tile."""
        key = (length, step_span, start_tile)
        sc = self._sched_cache.get(key)
        if sc is not None:
            return sc
        spec = self.cfg.attention_spec
        pats = {
            s.attn_pattern or spec.pattern
            for s in self.cfg.period_slots
            if s.mixer == "attn"
        }
        last = sparsity.page_last_reader_union(
            pats, length, spec.q_tile, self.page, pattern_arg=spec.pattern_arg
        )
        res = sparsity.page_residency(
            last, length, self.page, step_span, start_tile
        )
        peak_from = np.maximum.accumulate(res[::-1])[::-1]
        sc = _PagedSlot(last_reader=last, peak_from=peak_from, length=length)
        self._sched_cache[key] = sc
        return sc

    def _ring_schedule(self, length: int) -> _PagedSlot:
        """Retention schedule of a mod-window ring request: a FIXED set of
        ``min(ring_tiles, ceil(length / page))`` pages allocated at admission
        and held to retirement — slots are reused in phase, so no tile ever
        frees early and the reservation is exact by construction."""
        key = ("ring", length)
        sc = self._sched_cache.get(key)
        if sc is None:
            n = min(self.ring_tiles, -(-length // self.page))
            sc = _PagedSlot(
                last_reader=np.full(self.n_vtiles, length - 1, np.int64),
                peak_from=np.full(max(length, 1), n, np.int64),
                length=max(length, 1),
            )
            self._sched_cache[key] = sc
        return sc

    def _committed(self, active, sched, pos) -> int:
        """Sum of active requests' worst-case future residency — admission
        reserves against this so `PagePool.alloc` can never fail mid-stream
        (out-of-pages becomes FIFO backpressure at admission instead)."""
        return sum(
            sched[s].remaining_peak(int(pos[s]))
            for s in range(len(active))
            if active[s] is not None
        )

    def _ensure_writable(self, pool, pt, slot: int, lo_pos: int, hi_pos: int,
                         caches, owner: str = "?"):
        """Back every virtual tile overlapping positions [lo_pos, hi_pos)
        with a page this request may WRITE before the step that writes it:
        unbacked tiles allocate; tiles whose physical page is shared (an
        aliased prefix boundary, or a page the radix cache still owns)
        copy-on-write fork — pool fork + device row copy + table repoint —
        so the divergent write lands in a private copy instead of corrupting
        siblings.  Returns the (possibly copied-into) pools.

        Mod-window rings are a no-op here: the fixed ring pages were all
        allocated at admission, slots are reused in phase, and ring pages are
        never shared — there is nothing to back and nothing to fork."""
        if self.ring_tiles is not None:
            return caches
        for t in range(lo_pos // self.page, (hi_pos - 1) // self.page + 1):
            pid = int(pt[slot, t])
            if pid == self.pool_pages:
                pt[slot, t] = pool.alloc(owner)
            elif pool.page_refs(pid) > 1:
                new = pool.fork(pid, owner)
                caches = self.p_copy_fn(caches, jnp.int32(pid), jnp.int32(new))
                pt[slot, t] = new
        return caches

    def _free_dead(self, pool, pt, slot: int, sc: _PagedSlot, frontier: int,
                   owner: str | None = None):
        """Release pages whose last possible reader is behind the request's
        next query position — dense-causal never frees until retirement,
        window frees the out-of-window tail, butterfly frees every tile its
        remaining O(log n) stride pairs can no longer touch."""
        nt = len(sc.last_reader)
        for t in range(nt):
            if pt[slot, t] != self.pool_pages and sc.last_reader[t] < frontier:
                pool.release(int(pt[slot, t]), owner)
                pt[slot, t] = self.pool_pages

    def _free_all(self, pool, pt, slot: int, owner: str | None = None):
        for t in range(pt.shape[1]):
            if pt[slot, t] != self.pool_pages:
                pool.release(int(pt[slot, t]), owner)
                pt[slot, t] = self.pool_pages

    # -- prefix cache (radix tree over the page pool) ---------------------

    def _prefill_flop_count(self, pos0: int, t: int) -> float:
        """Analytic admission-side prefill work for ``t`` prompt tokens
        entering at absolute position ``pos0``: linear stack FLOPs plus the
        exact causal attention term.  This is what the --check-prefix gate
        compares — prefix hits skip the matched positions entirely, so the
        number scales with unique suffixes, not requests."""
        cfg = self.cfg
        n_attn = sum(
            1 for s in cfg.period_slots if s.mixer == "attn"
        ) * cfg.n_periods
        attn = 4.0 * cfg.n_heads * cfg.head_dim * n_attn * (
            t * pos0 + t * (t + 1) / 2.0
        )
        return t * M.model_flops_per_token(cfg, 1, mode="fwd") + attn

    def _match_prefix(self, prompt: np.ndarray) -> tuple[int, list[int]]:
        """Longest-prefix match at admission.  Caps the match at plen-1 (the
        last prompt token must run to produce first-token logits) and skips
        sub-page matches (no page to alias).  The caller must retain the
        returned pages before anything else can evict them.  ``prompt`` is
        the EFFECTIVE prompt: for a preempted request being resumed it is
        the original prompt plus every token already emitted, so the warm
        resume frontier is wherever the radix tree still covers it."""
        if self.radix is None:
            return 0, []
        plen = len(prompt)
        m, pages = self.radix.match(np.asarray(prompt, np.int32), plen - 1)
        if m < self.page:
            return 0, []
        return m, pages

    def _fits(self, need: int) -> int:
        """Reservation check against the pool, counting the radix cache's
        held pages; under pressure, LRU-evicts unreferenced cached prefixes.
        Returns the residual gap (<= 0 means the reservation fits)."""
        held = self.radix.held_pages if self.radix is not None else 0
        gap = need + held - self.pool_pages
        if gap > 0 and self.radix is not None:
            self.radix.evict(gap)
            gap = need + self.radix.held_pages - self.pool_pages
        return gap

    def _cache_pages(self, tokens: np.ndarray, pt, slot: int) -> None:
        """Hand ``tokens``' full, still-resident pages to the radix cache
        (shared ownership) — called on prompt completion AND on preemption,
        where ``tokens`` is the victim's written prefix so resume becomes a
        warm hit.  Retention may already have freed mid-prompt tiles
        (butterfly streams past them) — only the contiguous resident run
        from tile 0 is cacheable."""
        if self.radix is None:
            return
        k = len(tokens) // self.page
        run = 0
        while run < k and pt[slot, run] != self.pool_pages:
            run += 1
        if run:
            self.radix.insert(
                np.asarray(tokens[: run * self.page], np.int32),
                [int(pt[slot, t]) for t in range(run)],
            )

    def _suffix_prefill(self, prompt: np.ndarray, m: int, sc: _PagedSlot,
                        pool, pt, slot: int, caches, ct=None,
                        owner: str = "?"):
        """Admission-mode prefill of a prefix-cache hit: stream ONLY the
        unique suffix (positions m..plen-1) through the paged chunk entry
        point — prefill starts at the divergence frontier, attending the
        aliased prefix pages through the page table.  The first chunk
        CoW-forks the partially-shared boundary tile.  Dead tiles free
        between chunks (the unique-suffix reservation is priced at
        chunk-size spans, so the stream must keep that schedule).  Returns
        (first sampled token — device scalar, pools)."""
        C = self.chunk_size
        plen = len(prompt)
        p = m
        logits1 = None
        while p < plen:
            t = min(C, plen - p)
            caches = self._ensure_writable(pool, pt, slot, p, p + t, caches,
                                           owner)
            ctoks = np.zeros((1, C), np.int32)
            ctoks[0, :t] = prompt[p : p + t]
            kv_live = _next_bucket(p + t, self.cache_len)
            logits1, caches = self.p_chunk_fn(
                self.params, caches, jnp.asarray(ctoks),
                _to_device(pt[slot : slot + 1]), jnp.int32(p), jnp.int32(t),
                kv_live, ct=ct,
            )
            self.stats["chunk_calls"] = self.stats.get("chunk_calls", 0) + 1
            self.stats["prefill_tokens"] += t
            self.stats["prefill_flops"] += self._prefill_flop_count(p, t)
            p += t
            self._free_dead(pool, pt, slot, sc, p, owner)
        return jnp.argmax(logits1).astype(jnp.int32), caches

    def _cross_admit(self, r: Request, slot: int, ct, caches):
        """Admit the request's ENCODER side: key the frames, alias the cached
        read-only page range on a hit (a ``retain`` per page — CoW can never
        trigger because decode never writes a cross page), or allocate a
        fresh range and run the encoder once on a miss.  Returns the updated
        pools, or ``None`` when the cross pool cannot fit a new range even
        after evicting every unreferenced cached encoder (backpressure)."""
        frames = np.asarray(r.extras["frames"], np.float32)
        key = frames.tobytes()
        pages = self._cross_cache.get(key)
        if pages is not None:
            self._cross_cache.move_to_end(key)  # LRU touch
            for p in pages:
                self.cross_pool.retain(p, owner=f"req{r.uid}")
            ct[slot, : len(pages)] = pages
            self.stats["prefix_hits"] += 1
            self.stats["prefix_hit_tokens"] += self.cfg.enc_seq
            self.stats["encoder_hits"] = self.stats.get("encoder_hits", 0) + 1
            return caches
        n = self.cross_tiles
        if self.cross_pool.free_pages < n:
            # evict LRU cached encoders nobody references but the cache
            for k in [
                k for k in self._cross_cache
                if all(
                    self.cross_pool.page_refs(p) == 1
                    for p in self._cross_cache[k]
                )
            ]:
                for p in self._cross_cache.pop(k):
                    self.cross_pool.release(p, owner="encoder-cache")
                if self.cross_pool.free_pages >= n:
                    break
        if self.cross_pool.free_pages < n:
            return None
        pages = [self.cross_pool.alloc("encoder-cache") for _ in range(n)]
        ct[slot, :n] = pages
        caches = self.p_encode_fn(
            self.params, caches, jnp.asarray(frames)[None],
            _to_device(ct[slot : slot + 1]),
        )
        for p in pages:  # the request's own reference; alloc's is the cache's
            self.cross_pool.retain(p, owner=f"req{r.uid}")
        self._cross_cache[key] = pages
        self.stats["encode_calls"] = self.stats.get("encode_calls", 0) + 1
        return caches

    def _release_cross(self, ct, slot: int, owner: str | None = None) -> None:
        """Drop the request's references on its aliased cross page range."""
        for t in range(ct.shape[1]):
            if ct[slot, t] != self.cross_pages:
                self.cross_pool.release(int(ct[slot, t]), owner)
                ct[slot, t] = self.cross_pages

    # -- priority scheduling, preemption, SLO accounting ------------------

    @staticmethod
    def _eff_prompt(r: Request) -> np.ndarray:
        """The EFFECTIVE prompt of an admission: the original prompt plus
        every already-emitted token — non-empty ``generated`` only for a
        preempted request being resumed.  Greedy sampling makes the resume
        token-identical: re-prefilling the written prefix reconstructs the
        exact cache the victim lost (warm via the radix tree where its
        pages survived, cold recompute otherwise), and the next sampled
        token follows deterministically."""
        if not r.generated:
            return np.asarray(r.prompt, np.int32)
        return np.concatenate(
            [np.asarray(r.prompt, np.int32),
             np.asarray(r.generated, np.int32)]
        )

    def _stamp_emits(self, sinks: list[tuple[Request, int]],
                     clock: int) -> None:
        """Record the emission clock of every token pushed this step — the
        raw series per-request TTFT / inter-token latency aggregate from."""
        for r, _ in sinks:
            if r.ttft is None:
                r.ttft = clock - r.arrival
            r.emit_clocks.append(clock)

    def _finalize_slo(self, requests: list[Request],
                      q: _AdmitQueue) -> None:
        """End-of-run latency aggregation: p50/p99 TTFT and mean inter-token
        latency per priority class (engine-step clock units), the
        SLO-attainment fraction (1.0 when no SLO is configured), and the
        scheduler counters every loop shares."""
        per: dict[str, dict[str, list[float]]] = {}
        attained: list[bool] = []
        for r in requests:
            if not r.emit_clocks:
                continue
            t = float(r.ttft)
            gaps = np.diff(np.asarray(r.emit_clocks))
            itl = float(gaps.mean()) if len(gaps) else 0.0
            d = per.setdefault(r.priority, {"ttft": [], "itl": []})
            d["ttft"].append(t)
            d["itl"].append(itl)
            ok = True
            if self.slo_ttft is not None and t > self.slo_ttft:
                ok = False
            if self.slo_itl is not None and itl > self.slo_itl:
                ok = False
            attained.append(ok)
        slo = {}
        for prio in sorted(per):
            ts = np.asarray(per[prio]["ttft"])
            its = np.asarray(per[prio]["itl"])
            slo[prio] = {
                "n": int(len(ts)),
                "ttft_p50": float(np.percentile(ts, 50)),
                "ttft_p99": float(np.percentile(ts, 99)),
                "itl_p50": float(np.percentile(its, 50)),
                "itl_p99": float(np.percentile(its, 99)),
            }
        self.stats["slo"] = slo
        self.stats["slo_attainment"] = (
            float(np.mean(attained)) if attained else 1.0
        )
        self.stats["aging_promotions"] = q.promotions
        self.stats["starved_requests"] = sum(
            1 for r in requests if not r.emit_clocks
        )
        self.stats.setdefault("preemptions", 0)

    def _budget_draw(self, r: Request, rem_prompt: int, budget: int) -> int:
        """How many prompt tokens slot ``r`` may stream this step.
        Preemption-aware: a resumed victim is re-running prefill work the
        engine already paid for once, so its draw is capped at a
        ``resume_chunk_frac`` share of the step budget — fresh interactive
        admissions keep their first-token latency while the victim catches
        up (``resume_budget_capped`` counts the chunks the cap shrank)."""
        t = min(self.chunk_size, rem_prompt, budget)
        if r.preemptions > 0:
            cap = max(1, int(self.chunk_budget * self.resume_chunk_frac))
            if t > cap:
                t = cap
                self.stats["resume_budget_capped"] = (
                    self.stats.get("resume_budget_capped", 0) + 1
                )
        return t

    def _slot_owner(self, r: Request) -> str:
        """Owner label of a victim's pool references — the disaggregated
        router phase-qualifies it ("decode:reqN"), the single loop does not."""
        return f"req{r.uid}"

    def _preempt_slot(self, s: int, q: _AdmitQueue, fetch, pool, pt,
                      active, sched, parr, pos) -> None:
        """Evict the request in slot ``s``: flush the async token fetch (the
        snapshot must hold every emitted token), donate its written prefix's
        full resident pages to the radix tree (so resume is a warm hit),
        release its pool pages, and requeue it at its ORIGINAL arrival so
        its age — and any aging promotion — keeps accruing."""
        fetch.flush()
        r = active[s]
        written = self._eff_prompt(r)[: int(pos[s])]
        self._cache_pages(written, pt, s)
        self._free_all(pool, pt, s, owner=self._slot_owner(r))
        r.preemptions += 1
        self.stats["preemptions"] = self.stats.get("preemptions", 0) + 1
        active[s] = None
        sched[s] = None
        if parr is not None:
            parr[s] = None
        q.push(r)

    def _preempt_until(self, need, rank: int, q: _AdmitQueue, fetch, pool,
                       pt, active, sched, parr, pos, admit_pos,
                       admit_seq) -> int:
        """Preempt youngest lowest-priority victims until the reservation
        gap ``self._fits(need())`` closes or no eligible victim remains;
        returns the final gap (<= 0 means the admission fits).  A victim
        must hold a strictly worse RAW priority rank than the admitting
        request (aging changes admission order, never preemption power), be
        under the per-request preemption cap, and have advanced at least
        ``preempt_min_progress`` positions since its own admission — the
        cap bounds total evictions and the progress floor bounds wasted
        work, so preempt/resume cannot livelock."""
        gap = self._fits(need())
        while gap > 0:
            victim, vkey = None, None
            for s in range(len(active)):
                a = active[s]
                if a is None:
                    continue
                if _PRIORITY_RANK[a.priority] <= rank:
                    continue
                if a.preemptions >= self.max_preemptions:
                    continue
                if int(pos[s]) - int(admit_pos[s]) < self.preempt_min_progress:
                    continue
                key = (_PRIORITY_RANK[a.priority], int(a.arrival),
                       int(admit_seq[s]))
                if victim is None or key > vkey:
                    victim, vkey = s, key
            if victim is None:
                break
            self._preempt_slot(victim, q, fetch, pool, pt, active, sched,
                               parr, pos)
            gap = self._fits(need())
        return gap

    def close(self) -> None:
        """Release the engine-held cache state (radix tree references, cached
        encoder cross ranges) and check the pools drain to zero.  The pools
        and the prefix caches PERSIST across ``run()`` calls — a warm second
        run alias-hits the first run's prompts — so the end-of-run drain
        assertion of the per-run engines lives here instead.

        Idempotent: a second ``close()`` after a CLEAN first one is a no-op.
        A close that raised (leak detected) stays re-runnable so a caller
        can release the stragglers and verify the drain; the leak error
        names the holders (:meth:`PagePool.holders` labels) so the bug site
        is attributable without a refcount bisect."""
        if self._closed or not self.paged:
            self._closed = True
            return
        if self.radix is not None:
            self.radix.clear()
        if self.cross_pages is not None:
            for pages in self._cross_cache.values():
                for p in pages:
                    self.cross_pool.release(p, owner="encoder-cache")
            self._cross_cache.clear()
            self.cross_pool.close(
                context="after close() released the encoder cache"
            )
        self.pool.close(context="after close() released the radix tree")
        self._closed = True

    def __enter__(self) -> "ServeLoop":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is not None:
            # an exception is already propagating: close best-effort, but a
            # leak (requests mid-flight when the body raised) must not mask
            # the original error
            try:
                self.close()
            except RuntimeError:
                pass
            return False
        self.close()
        return False

    def _finish_paged_run(self, pool) -> None:
        """End-of-run bookkeeping shared by both paged loops: surface the
        pool and prefix-cache counters.  Requests have released all their
        references by now; what remains in ``in_use`` is exactly the engine-
        held cache state (radix tree + encoder cross ranges), which persists
        for the next run and drains in :meth:`close`."""
        self.stats["pool_pages"] = self.pool_pages
        self.stats["pool_peak_pages"] = pool.peak_in_use
        self.stats["page_allocs"] = pool.alloc_count
        self.stats["cow_forks"] = pool.fork_count
        if pool.n_shards > 1:
            self.stats["pool_shards"] = pool.n_shards
            self.stats["shard_peak_pages"] = list(pool.shard_peak_in_use)
        if self.radix is not None:
            self.stats["prefix_cached_pages_end"] = self.radix.held_pages
            self.stats["prefix_inserted_pages"] = self.radix.inserted_pages
            self.stats["prefix_evicted_pages"] = self.radix.evicted_pages
        if self.cross_pages is not None:
            self.stats.setdefault("encode_calls", 0)
            self.stats["cross_pool_pages"] = self.cross_pages
            self.stats["cross_pool_peak_pages"] = self.cross_pool.peak_in_use
            self.stats["cross_cached_ranges_end"] = len(self._cross_cache)

    def _run_admission(self, requests: list[Request]) -> list[Request]:
        """Admission-prefill engine: per-slot prefill + cache insert, then
        ragged decode steps; finished requests retire immediately and free
        their slot — but every admission stalls all live decode slots for
        one blocking batch-1 prefill (counted in ``admission_stall_steps``).
        """
        q = _AdmitQueue(requests, self.aging_steps, self.fifo)
        active: list[Request | None] = [None] * self.batch
        pos = np.zeros(self.batch, np.int32)  # next write position per slot
        remaining = np.zeros(self.batch, np.int32)  # decode tokens still owed
        nxt = jnp.zeros((self.batch,), jnp.int32)  # device feedback tokens
        fetch = _AsyncTokens(lag=1)
        self.stats = {
            "prefill_calls": 0, "decode_steps": 0, "admission_stall_steps": 0,
        }
        clock = 0  # admission clock: decode steps + idle ticks (arrivals)
        with self.mesh:
            caches = self._zero_caches()
            while len(q) or any(r is not None for r in active):
                # admit: fill free slots (waves only, under static batching)
                may_admit = not self.static_batching or all(
                    r is None for r in active
                )
                if may_admit:
                    for slot in range(self.batch):
                        if active[slot] is not None:
                            continue
                        r = q.peek(clock)
                        if r is None:
                            break  # nothing in the queue has arrived yet
                        q.pop(r, clock)
                        if any(a is not None for a in active):
                            # live decode slots idle for this whole prefill —
                            # the stall the chunked engine exists to remove
                            self.stats["admission_stall_steps"] += 1
                        tok, wave = self._prefill_one(r)
                        self._stamp_emits([(r, 0)], clock)
                        fetch.push(tok, [(r, 0)])
                        if r.max_new <= 1:
                            continue  # done at prefill; slot stays free
                        caches = self._insert(caches, wave, jnp.int32(slot))
                        active[slot] = r
                        pos[slot] = len(r.prompt)
                        remaining[slot] = r.max_new - 1
                        nxt = nxt.at[slot].set(tok)
                if not any(r is not None for r in active):
                    clock += 1  # idle tick: waiting on arrivals
                    continue
                # one ragged decode step for the whole batch; attention
                # streams only the live cache prefix (bucketed so each bucket
                # compiles once) — a short wave on a deep cache reads its own
                # tiles, not the padded cache.  Ring caches keep their own
                # mod-window layout and stream the whole (window-sized) ring.
                kv_live = None
                if not self.cfg.sliding_window:
                    hot = max(int(pos[s]) for s in range(self.batch)
                              if active[s] is not None) + 1
                    kv_live = _next_bucket(hot, self.cache_len)
                    self.stats["decode_kv_live_max"] = max(
                        self.stats.get("decode_kv_live_max", 0), kv_live
                    )
                logits, caches = self.decode_fn(
                    self.params, caches, nxt[:, None], _to_device(pos), kv_live,
                )
                self.stats["decode_steps"] += 1
                clock += 1
                toks = jnp.argmax(logits, -1).astype(jnp.int32)
                sinks = []
                for slot in range(self.batch):
                    r = active[slot]
                    if r is None:
                        continue
                    sinks.append((r, slot))
                    pos[slot] += 1
                    remaining[slot] -= 1
                    if remaining[slot] <= 0:
                        active[slot] = None  # evict: slot frees for the queue
                self._stamp_emits(sinks, clock)
                fetch.push(toks, sinks)
                nxt = toks
        fetch.flush()
        self._finalize_slo(requests, q)
        return requests

    def _run_chunked(self, requests: list[Request]) -> list[Request]:
        """Mixed-step engine: every iteration advances ALL slots — one (B, 1)
        decode wave samples every decoding row, then each mid-prompt row
        streams one chunk into the shared cache through a (1, C) slot-chunk
        call — so a long admission never stalls the batch, and decode steps
        stay bucketed at the decode rows' own live-cache depth while the
        prompt streams at its own."""
        B, C = self.batch, self.chunk_size
        q = _AdmitQueue(requests, self.aging_steps, self.fifo)
        active: list[Request | None] = [None] * B
        pos = np.zeros(B, np.int32)  # next cache write position per slot
        consumed = np.zeros(B, np.int32)  # prompt tokens consumed per slot
        remaining = np.zeros(B, np.int32)  # decode tokens still owed
        nxt = jnp.zeros((B,), jnp.int32)  # device feedback tokens
        zeros_b1 = jnp.zeros((B, 1), jnp.int32)
        fetch = _AsyncTokens(lag=1)
        self.stats = {
            "prefill_calls": 0, "mixed_steps": 0, "chunk_calls": 0,
            "decode_steps": 0, "prefill_tokens": 0, "decode_tokens": 0,
            "decode_stall_steps": 0, "overlap_steps": 0,
        }
        clock = 0
        rr = 0  # round-robin offset: fair prefill budget across slots
        with self.mesh:
            caches = self._zero_caches()
            while len(q) or any(r is not None for r in active):
                # admission is free: a freed slot starts consuming the next
                # arrived request's chunks on the very next mixed step
                for slot in range(B):
                    if active[slot] is not None:
                        continue
                    r = q.peek(clock)
                    if r is None:
                        break  # nothing in the queue has arrived yet
                    q.pop(r, clock)
                    active[slot] = r
                    pos[slot] = 0
                    consumed[slot] = 0
                    remaining[slot] = r.max_new
                if not any(r is not None for r in active):
                    clock += 1  # idle tick: waiting on arrivals
                    continue
                # schedule: decode rows always advance; prompt rows split the
                # per-step chunk budget under a round-robin rotation
                eligible = [
                    s for s in range(B)
                    if active[s] is not None
                    and len(active[s].prompt) - consumed[s] <= 0
                ]
                use_nxt = np.zeros(B, bool)
                chunk_t = np.zeros(B, np.int32)
                budget = self.chunk_budget
                # interactive rows split the chunk budget ahead of batch
                # rows; the rotation keeps it fair within a class (and IS
                # the whole order under uniform priority / fifo scheduling)
                order = sorted(
                    range(B),
                    key=lambda s: (
                        0 if self.fifo or active[s] is None
                        else _PRIORITY_RANK[active[s].priority],
                        (s - rr) % B,
                    ),
                )
                for slot in order:
                    r = active[slot]
                    if r is None:
                        continue
                    rem_prompt = len(r.prompt) - consumed[slot]
                    if rem_prompt > 0:
                        t = min(C, rem_prompt, budget)
                        if t <= 0:
                            continue  # budget-starved this step; retries next
                        chunk_t[slot] = t
                        budget -= t
                    else:
                        use_nxt[slot] = True  # decode rows: never budget-gated
                rr = (rr + 1) % B
                clock += 1
                self.stats["mixed_steps"] += 1
                dec_rows = [s for s in range(B) if use_nxt[s]]
                chunk_rows = [s for s in range(B) if chunk_t[s] > 0]
                if any(s not in dec_rows for s in eligible):
                    # observational, not definitional: trips if a scheduler
                    # change ever gates a decode-eligible row (e.g. on the
                    # chunk budget) — the CI gate asserts this stays 0
                    self.stats["decode_stall_steps"] += 1
                if dec_rows and chunk_rows:
                    self.stats["overlap_steps"] += 1  # the §V-A overlap
                # (a) decode wave — mixed_step at (B, 1), bucketed by the
                # decode rows' own frontier (a short request decoding next to
                # a 4k prompt mid-prefill still reads a shallow cache)
                if dec_rows:
                    ntok_a = np.where(use_nxt, 1, 0).astype(np.int32)
                    hot = max(int(pos[s]) + 1 for s in dec_rows)
                    kv_live = _next_bucket(hot, self.cache_len)
                    self.stats["decode_kv_live_max"] = max(
                        self.stats.get("decode_kv_live_max", 0), kv_live
                    )
                    logits, caches = self.mixed1_fn(
                        self.params, caches, zeros_b1, nxt,
                        jnp.asarray(use_nxt), _to_device(pos),
                        jnp.asarray(ntok_a), kv_live,
                    )
                    toks = jnp.argmax(logits, -1).astype(jnp.int32)
                    self.stats["decode_steps"] += 1
                    self.stats["decode_tokens"] += len(dec_rows)
                    sinks = []
                    for slot in dec_rows:
                        r = active[slot]
                        sinks.append((r, slot))
                        pos[slot] += 1
                        remaining[slot] -= 1
                        if remaining[slot] <= 0:
                            active[slot] = None
                    self._stamp_emits(sinks, clock)
                    fetch.push(toks, sinks)
                    nxt = jnp.where(jnp.asarray(use_nxt), toks, nxt)
                # (b) prompt chunks — mixed_step at (1, C) per mid-prompt
                # row, streaming into the slot's rows of the shared cache at
                # the prompt's own frontier bucket
                for slot in chunk_rows:
                    r = active[slot]
                    t = int(chunk_t[slot])
                    ctoks = np.zeros((1, C), np.int32)
                    ctoks[0, :t] = r.prompt[consumed[slot] : consumed[slot] + t]
                    kv_live = _next_bucket(int(pos[slot]) + t, self.cache_len)
                    logits1, caches = self.chunk_fn(
                        self.params, caches, jnp.asarray(ctoks),
                        jnp.int32(slot), jnp.int32(pos[slot]), jnp.int32(t),
                        kv_live,
                    )
                    self.stats["chunk_calls"] += 1
                    self.stats["prefill_tokens"] += t
                    pos[slot] += t
                    consumed[slot] += t
                    if consumed[slot] == len(r.prompt):
                        # the chunk that finishes the prompt samples the
                        # first generated token (logits at ntok-1)
                        tok1 = jnp.argmax(logits1).astype(jnp.int32)
                        self._stamp_emits([(r, 0)], clock)
                        fetch.push(tok1, [(r, 0)])
                        nxt = nxt.at[slot].set(tok1)
                        remaining[slot] -= 1
                        if remaining[slot] <= 0:
                            active[slot] = None
        fetch.flush()
        self._finalize_slo(requests, q)
        return requests

    def _run_paged_admission(self, requests: list[Request]) -> list[Request]:
        """Admission-by-pages engine: per-request batch-1 prefill scattered
        straight into the page pool through the request's page-table row,
        then ragged paged decode waves.  A free SLOT no longer suffices for
        admission — the request must also reserve its worst-case resident
        page count; otherwise it backpressures in FIFO order until decode
        frees pages.  Resident HBM is the pool, not batch x cache_len.

        With the radix prefix cache on, admission first longest-prefix
        matches the prompt: a hit aliases the cached pages into the page
        table, reserves only the unique-suffix peak, and prefills JUST the
        suffix from the divergence frontier (via the chunk entry point)."""
        B = self.batch
        q = _AdmitQueue(requests, self.aging_steps, self.fifo)
        active: list[Request | None] = [None] * B
        sched: list[_PagedSlot | None] = [None] * B
        pos = np.zeros(B, np.int32)
        remaining = np.zeros(B, np.int32)
        admit_pos = np.zeros(B, np.int32)  # pos at admission: progress floor
        admit_seq = np.zeros(B, np.int64)  # admission order: victim tiebreak
        aseq = 0
        nxt = jnp.zeros((B,), jnp.int32)
        pt = np.full((B, self.n_vtiles), self.pool_pages, np.int32)
        pool = self.pool
        ct = None
        if self.cross_pages is not None:
            ct = np.full((B, self.cross_tiles), self.cross_pages, np.int32)
        fetch = _AsyncTokens(lag=1)
        self.stats = {
            "prefill_calls": 0, "decode_steps": 0, "admission_stall_steps": 0,
            "admission_backpressure": 0, "max_concurrent": 0,
            "prefill_tokens": 0, "prefill_flops": 0.0,
            "prefix_hits": 0, "prefix_hit_tokens": 0,
            "preemptions": 0, "resumes": 0, "resume_warm_hits": 0,
        }
        clock = 0
        with self.mesh:
            caches = (
                self._pools if self._pools is not None else self._zero_pools()
            )
            while len(q) or any(r is not None for r in active):
                for slot in range(B):
                    if active[slot] is not None:
                        continue
                    r = q.peek(clock)
                    if r is None:
                        break  # nothing in the queue has arrived yet
                    pr = self._eff_prompt(r)  # prompt + resumed tokens
                    plen = len(pr)
                    mn = r.max_new - len(r.generated)
                    L = plen + mn - 1  # == original prompt + max_new - 1
                    own = f"req{r.uid}"
                    rank = _PRIORITY_RANK[r.priority]
                    # prefix hit: alias cached pages, reserve the unique
                    # suffix only; fall back to a cold admission if even
                    # that reservation cannot fit (after preempting any
                    # eligible lower-priority victims)
                    m, spages = self._match_prefix(pr)
                    if m:
                        for p in spages:
                            pool.retain(p, owner=own)
                        sc = self._paged_schedule(
                            L, step_span=self.chunk_size,
                            start_tile=m // self.page,
                        )
                        need = lambda: (
                            self._committed(active, sched, pos)
                            + sc.remaining_peak(m)
                        )
                        gap = self._fits(need())
                        if gap > 0 and self.preemptible:
                            gap = self._preempt_until(
                                need, rank, q, fetch, pool, pt, active,
                                sched, None, pos, admit_pos, admit_seq,
                            )
                        if gap > 0:
                            for p in spages:
                                pool.release(p, owner=own)
                            cold_peak = self._paged_schedule(
                                L, step_span=(
                                    self.chunk_size
                                    if self.cross_pages is not None else plen
                                ),
                            ).remaining_peak(0)
                            if cold_peak < sc.remaining_peak(m):
                                # cold genuinely cheaper (retention frees
                                # tiles the alias would pin): retry cold
                                m, spages = 0, []
                            else:
                                # cold could not fit either — and its _fits
                                # would evict the very prefix (a preemption
                                # victim's donated pages) that makes the
                                # eventual resume warm
                                self.stats["admission_backpressure"] += 1
                                break
                    if not m:
                        if self.ring_tiles is not None:
                            sc = self._ring_schedule(L)
                        elif self.cross_pages is not None:
                            # encdec streams the decoder prompt through the
                            # chunk entry point — spans are chunk-sized
                            sc = self._paged_schedule(
                                L, step_span=self.chunk_size
                            )
                        else:
                            sc = self._paged_schedule(L, step_span=plen)
                        need = lambda: (
                            self._committed(active, sched, pos)
                            + sc.remaining_peak(0)
                        )
                        gap = self._fits(need())
                        if gap > 0 and self.preemptible:
                            gap = self._preempt_until(
                                need, rank, q, fetch, pool, pt, active,
                                sched, None, pos, admit_pos, admit_seq,
                            )
                        if gap > 0:
                            # out of pages: the head waits for decode to free
                            # some — backpressure, not an error
                            self.stats["admission_backpressure"] += 1
                            break
                    if self.cross_pages is not None:
                        nc = self._cross_admit(r, slot, ct, caches)
                        if nc is None:
                            # no cross range free for a new encoder input
                            self.stats["admission_backpressure"] += 1
                            break
                        caches = nc
                    q.pop(r, clock)
                    if r.preemptions:  # a victim re-admitting (possibly
                        self.stats["resumes"] += 1  # mid-prefill, no tokens)
                        if m:
                            self.stats["resume_warm_hits"] += 1
                    if any(a is not None for a in active):
                        self.stats["admission_stall_steps"] += 1
                    ct_row = (
                        None if ct is None else _to_device(ct[slot:slot + 1])
                    )
                    if m:
                        for i, p in enumerate(spages):
                            pt[slot, i] = p
                        self.stats["prefix_hits"] += 1
                        self.stats["prefix_hit_tokens"] += m
                        tok, caches = self._suffix_prefill(
                            pr, m, sc, pool, pt, slot, caches, owner=own
                        )
                    elif self.ring_tiles is not None or ct is not None:
                        # mod-window rings allocate their fixed page set up
                        # front; both rings and encoder-decoder admissions
                        # then STREAM the prompt through the chunk entry
                        # point (a monolithic paged prefill would wrap the
                        # ring / has no cross-table path)
                        if self.ring_tiles is not None:
                            for t in range(
                                min(self.ring_tiles, -(-L // self.page))
                            ):
                                pt[slot, t] = pool.alloc(own)
                        tok, caches = self._suffix_prefill(
                            pr, 0, sc, pool, pt, slot, caches, ct=ct_row,
                            owner=own,
                        )
                    else:
                        caches = self._ensure_writable(
                            pool, pt, slot, 0, plen, caches, own
                        )
                        bucket = _next_bucket(plen, self.cache_len)
                        toks = np.zeros((1, bucket), np.int32)
                        toks[0, :plen] = pr
                        logits, caches = self.p_prefill_fn(
                            self.params, caches, {"tokens": jnp.asarray(toks)},
                            jnp.asarray([plen], jnp.int32),
                            _to_device(pt[slot : slot + 1]),
                        )
                        self.stats["prefill_calls"] += 1
                        self.stats["prefill_tokens"] += plen
                        self.stats["prefill_flops"] += (
                            self._prefill_flop_count(0, plen)
                        )
                        tok = jnp.argmax(logits[0]).astype(jnp.int32)
                    self._stamp_emits([(r, 0)], clock)
                    fetch.push(tok, [(r, 0)])
                    self._cache_pages(pr, pt, slot)
                    if mn <= 1:
                        self._free_all(pool, pt, slot, own)
                        if ct is not None:
                            self._release_cross(ct, slot, own)
                        continue  # done at prefill; slot and pages free
                    self._free_dead(pool, pt, slot, sc, plen, own)
                    active[slot] = r
                    sched[slot] = sc
                    pos[slot] = plen
                    admit_pos[slot] = plen
                    admit_seq[slot] = aseq
                    aseq += 1
                    remaining[slot] = mn - 1
                    nxt = nxt.at[slot].set(tok)
                self.stats["max_concurrent"] = max(
                    self.stats["max_concurrent"],
                    sum(a is not None for a in active),
                )
                if not any(r is not None for r in active):
                    clock += 1
                    continue
                # ragged paged decode wave: back each row's write tile (CoW-
                # forking a still-shared boundary tile), then every row
                # streams its own live pages through its page-table row at
                # the bucketed virtual depth
                for slot in range(B):
                    if active[slot] is not None:
                        caches = self._ensure_writable(
                            pool, pt, slot, int(pos[slot]),
                            int(pos[slot]) + 1, caches,
                            f"req{active[slot].uid}",
                        )
                if self.ring_tiles is not None:
                    # the ring streams its fixed window-sized page set and
                    # positions are unbounded — no live-depth bucketing
                    kv_live = None
                else:
                    hot = max(int(pos[s]) for s in range(B)
                              if active[s] is not None) + 1
                    kv_live = _next_bucket(hot, self.cache_len)
                    self.stats["decode_kv_live_max"] = max(
                        self.stats.get("decode_kv_live_max", 0), kv_live
                    )
                logits, caches = self.p_decode_fn(
                    self.params, caches, nxt[:, None], _to_device(pos),
                    _to_device(pt), kv_live,
                    **({} if ct is None else {"ct": _to_device(ct)}),
                )
                self.stats["decode_steps"] += 1
                clock += 1
                toks = jnp.argmax(logits, -1).astype(jnp.int32)
                sinks = []
                for slot in range(B):
                    r = active[slot]
                    if r is None:
                        continue
                    sinks.append((r, slot))
                    pos[slot] += 1
                    remaining[slot] -= 1
                    if remaining[slot] <= 0:
                        self._free_all(pool, pt, slot, f"req{r.uid}")
                        if ct is not None:
                            self._release_cross(ct, slot, f"req{r.uid}")
                        active[slot] = None
                        sched[slot] = None
                    else:
                        self._free_dead(
                            pool, pt, slot, sched[slot], int(pos[slot]),
                            f"req{r.uid}",
                        )
                self._stamp_emits(sinks, clock)
                fetch.push(toks, sinks)
                nxt = toks
        fetch.flush()
        self._pools = caches
        self._finish_paged_run(pool)
        self._finalize_slo(requests, q)
        return requests

    def _run_paged_chunked(self, requests: list[Request]) -> list[Request]:
        """Mixed-step engine over the page pool: the decode wave and the
        per-row prompt chunks of the chunked scheduler, with cache writes and
        reads indirected through per-request page tables.  Pages allocate
        lazily at each row's write frontier and free as soon as the
        retention schedule says no future query can read them — a butterfly
        prompt releases most of its tiles WHILE it streams in, which is the
        capacity win the paged_capacity benchmark measures.

        A radix prefix-cache hit admits at the divergence frontier: the
        matched pages alias into the slot's page table, ``pos``/``consumed``
        start at the matched length, and the reservation covers only the
        unique suffix — chunk streaming then picks up mid-prompt exactly as
        if the prefix had already streamed."""
        B, C = self.batch, self.chunk_size
        q = _AdmitQueue(requests, self.aging_steps, self.fifo)
        active: list[Request | None] = [None] * B
        sched: list[_PagedSlot | None] = [None] * B
        parr: list[np.ndarray | None] = [None] * B  # effective prompt per slot
        pos = np.zeros(B, np.int32)
        consumed = np.zeros(B, np.int32)
        remaining = np.zeros(B, np.int32)
        admit_pos = np.zeros(B, np.int32)  # pos at admission: progress floor
        admit_seq = np.zeros(B, np.int64)  # admission order: victim tiebreak
        aseq = 0
        nxt = jnp.zeros((B,), jnp.int32)
        pt = np.full((B, self.n_vtiles), self.pool_pages, np.int32)
        pool = self.pool
        ct = None
        if self.cross_pages is not None:
            ct = np.full((B, self.cross_tiles), self.cross_pages, np.int32)
        fetch = _AsyncTokens(lag=1, phase=self._phase)
        self.stats = {
            "prefill_calls": 0, "mixed_steps": 0, "chunk_calls": 0,
            "decode_steps": 0, "prefill_tokens": 0, "decode_tokens": 0,
            "decode_stall_steps": 0, "overlap_steps": 0,
            "admission_backpressure": 0, "max_concurrent": 0,
            "prefill_flops": 0.0, "prefix_hits": 0, "prefix_hit_tokens": 0,
            "preemptions": 0, "resumes": 0, "resume_warm_hits": 0,
        }
        for _, total, longest in _PHASES.values():
            self.stats[total] = 0.0
            if longest is not None:
                self.stats[longest] = 0.0
        clock = 0
        rr = 0
        with self.mesh:
            caches = (
                self._pools if self._pools is not None else self._zero_pools()
            )
            while len(q) or any(r is not None for r in active):
                with self._phase("step", step=clock):
                    # admission: a free slot AND a page reservation — the
                    # page budget, not the slot count, is the capacity limit;
                    # a higher-priority request that cannot reserve may evict
                    # the youngest lowest-priority active request instead of
                    # waiting
                    with self._phase("admit"):
                        for slot in range(B):
                            if active[slot] is not None:
                                continue
                            r = q.peek(clock)
                            if r is None:
                                break  # nothing in the queue has arrived yet
                            pr = self._eff_prompt(r)  # prompt + resumed tokens
                            L = len(pr) + (r.max_new - len(r.generated)) - 1
                            own = f"req{r.uid}"
                            rank = _PRIORITY_RANK[r.priority]
                            m, spages = self._match_prefix(pr)
                            if m:
                                for p in spages:
                                    pool.retain(p, owner=own)
                                sc = self._paged_schedule(
                                    L, step_span=C, start_tile=m // self.page
                                )
                                need = lambda: (
                                    self._committed(active, sched, pos)
                                    + sc.remaining_peak(m)
                                )
                                gap = self._fits(need())
                                if gap > 0 and self.preemptible:
                                    gap = self._preempt_until(
                                        need, rank, q, fetch, pool, pt, active,
                                        sched, parr, pos, admit_pos, admit_seq,
                                    )
                                if gap > 0:
                                    for p in spages:
                                        pool.release(p, owner=own)
                                    cold_peak = self._paged_schedule(
                                        L, step_span=C
                                    ).remaining_peak(0)
                                    if cold_peak < sc.remaining_peak(m):
                                        # cold genuinely cheaper (retention
                                        # frees tiles the alias would pin):
                                        # retry cold
                                        m, spages = 0, []
                                    else:
                                        # cold could not fit either — and its
                                        # _fits would evict the very prefix
                                        # (a preemption victim's donated
                                        # pages) that makes the eventual
                                        # resume warm
                                        self.stats[
                                            "admission_backpressure"] += 1
                                        break
                            if not m:
                                sc = (
                                    self._ring_schedule(L)
                                    if self.ring_tiles is not None
                                    else self._paged_schedule(L, step_span=C)
                                )
                                need = lambda: (
                                    self._committed(active, sched, pos)
                                    + sc.remaining_peak(0)
                                )
                                gap = self._fits(need())
                                if gap > 0 and self.preemptible:
                                    gap = self._preempt_until(
                                        need, rank, q, fetch, pool, pt, active,
                                        sched, parr, pos, admit_pos, admit_seq,
                                    )
                                if gap > 0:
                                    self.stats["admission_backpressure"] += 1
                                    break
                            if self.cross_pages is not None:
                                nc = self._cross_admit(r, slot, ct, caches)
                                if nc is None:
                                    self.stats["admission_backpressure"] += 1
                                    break
                                caches = nc
                            q.pop(r, clock)
                            if r.preemptions:
                                # a victim re-admitting (possibly
                                # mid-prefill, no tokens)
                                self.stats["resumes"] += 1
                                if m:
                                    self.stats["resume_warm_hits"] += 1
                            if m:
                                for i, p in enumerate(spages):
                                    pt[slot, i] = p
                                self.stats["prefix_hits"] += 1
                                self.stats["prefix_hit_tokens"] += m
                            elif self.ring_tiles is not None:
                                # the fixed mod-window page set, allocated
                                # up front — chunk streaming reuses the
                                # slots in phase
                                n_ring = min(self.ring_tiles,
                                             -(-L // self.page))
                                for t in range(n_ring):
                                    pt[slot, t] = pool.alloc(own)
                            active[slot] = r
                            sched[slot] = sc
                            parr[slot] = pr
                            pos[slot] = m
                            consumed[slot] = m
                            admit_pos[slot] = m
                            admit_seq[slot] = aseq
                            aseq += 1
                            remaining[slot] = r.max_new - len(r.generated)
                    self.stats["max_concurrent"] = max(
                        self.stats["max_concurrent"],
                        sum(a is not None for a in active),
                    )
                    if not any(r is not None for r in active):
                        clock += 1
                        continue
                    eligible = [
                        s for s in range(B)
                        if active[s] is not None
                        and len(parr[s]) - consumed[s] <= 0
                    ]
                    use_nxt = np.zeros(B, bool)
                    chunk_t = np.zeros(B, np.int32)
                    budget = self.chunk_budget
                    # interactive rows split the chunk budget ahead of batch
                    # rows; the rotation keeps it fair within a class (and IS
                    # the whole order under uniform priority / fifo scheduling)
                    order = sorted(
                        range(B),
                        key=lambda s: (
                            0 if self.fifo or active[s] is None
                            else _PRIORITY_RANK[active[s].priority],
                            (s - rr) % B,
                        ),
                    )
                    for slot in order:
                        r = active[slot]
                        if r is None:
                            continue
                        rem_prompt = len(parr[slot]) - consumed[slot]
                        if rem_prompt > 0:
                            t = self._budget_draw(r, rem_prompt, budget)
                            if t <= 0:
                                continue
                            chunk_t[slot] = t
                            budget -= t
                        else:
                            use_nxt[slot] = True
                    rr = (rr + 1) % B
                    clock += 1
                    self.stats["mixed_steps"] += 1
                    dec_rows = [s for s in range(B) if use_nxt[s]]
                    chunk_rows = [s for s in range(B) if chunk_t[s] > 0]
                    if any(s not in dec_rows for s in eligible):
                        self.stats["decode_stall_steps"] += 1
                    if dec_rows and chunk_rows:
                        self.stats["overlap_steps"] += 1
                    # (a) paged decode wave: every decoding row advances
                    # through the decode grid; non-decoding rows run with a
                    # sentinel page-table row so their garbage write DROPS —
                    # a mid-prompt row's frontier tile may alias a shared
                    # prefix page, which an unmasked write would corrupt for
                    # every sibling
                    if dec_rows:
                        if self.ring_tiles is not None:
                            kv_live = None  # ring positions are unbounded
                        else:
                            hot = max(int(pos[s]) + 1 for s in dec_rows)
                            kv_live = _next_bucket(hot, self.cache_len)
                            self.stats["decode_kv_live_max"] = max(
                                self.stats.get("decode_kv_live_max", 0),
                                kv_live,
                            )
                        with self._phase("decode", rows=len(dec_rows),
                                         kv_live=kv_live):
                            for slot in dec_rows:
                                caches = self._ensure_writable(
                                    pool, pt, slot, int(pos[slot]),
                                    int(pos[slot]) + 1, caches,
                                    f"req{active[slot].uid}",
                                )
                            use = np.asarray(use_nxt)
                            pt_wave = np.where(
                                use[:, None], pt, np.int32(self.pool_pages)
                            ).astype(np.int32)
                            cross = ({} if ct is None
                                     else {"ct": _to_device(ct)})
                            logits, caches = self.p_decode_fn(
                                self.params, caches, nxt[:, None],
                                _to_device(pos), jnp.asarray(pt_wave), kv_live,
                                **cross,
                            )
                            toks = jnp.argmax(logits, -1).astype(jnp.int32)
                            self.stats["decode_steps"] += 1
                            self.stats["decode_tokens"] += len(dec_rows)
                            sinks = []
                            for slot in dec_rows:
                                r = active[slot]
                                own = f"req{r.uid}"
                                sinks.append((r, slot))
                                pos[slot] += 1
                                remaining[slot] -= 1
                                if remaining[slot] <= 0:
                                    self._free_all(pool, pt, slot, own)
                                    if ct is not None:
                                        self._release_cross(ct, slot, own)
                                    active[slot] = None
                                    sched[slot] = None
                                    parr[slot] = None
                                else:
                                    self._free_dead(
                                        pool, pt, slot, sched[slot],
                                        int(pos[slot]), own,
                                    )
                            self._stamp_emits(sinks, clock)
                            fetch.push(toks, sinks)
                            nxt = jnp.where(jnp.asarray(use_nxt), toks, nxt)
                    # (b) prompt chunks through the paged chunk grid: allocate
                    # the chunk's tiles, stream it into the pool, then free
                    # whatever the pattern says is already dead
                    for slot in chunk_rows:
                        r = active[slot]
                        own = f"req{r.uid}"
                        t = int(chunk_t[slot])
                        p0 = int(pos[slot])
                        kv_live = _next_bucket(p0 + t, self.cache_len)
                        with self._phase("chunk", req=r.uid, tokens=t,
                                         kv_live=kv_live):
                            caches = self._ensure_writable(
                                pool, pt, slot, p0, p0 + t, caches, own,
                            )
                            ctoks = np.zeros((1, C), np.int32)
                            ctoks[0, :t] = parr[slot][
                                consumed[slot] : consumed[slot] + t
                            ]
                            ct_row = (None if ct is None
                                      else _to_device(ct[slot : slot + 1]))
                            logits1, caches = self.p_chunk_fn(
                                self.params, caches, jnp.asarray(ctoks),
                                _to_device(pt[slot : slot + 1]),
                                jnp.int32(p0), jnp.int32(t), kv_live,
                                ct=ct_row,
                            )
                            self.stats["chunk_calls"] += 1
                            self.stats["prefill_tokens"] += t
                            self.stats["prefill_flops"] += (
                                self._prefill_flop_count(p0, t)
                            )
                            pos[slot] += t
                            consumed[slot] += t
                            if consumed[slot] == len(parr[slot]):
                                self._cache_pages(parr[slot], pt, slot)
                                tok1 = jnp.argmax(logits1).astype(jnp.int32)
                                self._stamp_emits([(r, 0)], clock)
                                fetch.push(tok1, [(r, 0)])
                                nxt = nxt.at[slot].set(tok1)
                                remaining[slot] -= 1
                                if remaining[slot] <= 0:
                                    self._free_all(pool, pt, slot, own)
                                    if ct is not None:
                                        self._release_cross(ct, slot, own)
                                    active[slot] = None
                                    sched[slot] = None
                                    parr[slot] = None
                                    continue
                            self._free_dead(pool, pt, slot, sched[slot],
                                            int(pos[slot]), own)
        fetch.flush()
        self._pools = caches
        self._finish_paged_run(pool)
        self._finalize_slo(requests, q)
        return requests
