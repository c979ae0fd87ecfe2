"""One benchmark run: load a cell by name, serve its traffic through the
program's ``ServeLoop.run`` in timed rounds, read the metrics, check the
served tokens against the plain reference, print one result line.

Everything that belongs to a configuration, a traffic mix, a metric or a
cell's limits is a file under ``bench/`` found by the name
``BENCHMARK.json`` gives it: ``configs/<file>``, ``traffic/<mix>.json``,
``metrics/<metric>.py`` and ``limits/<cell>.json``.  A configuration's
model code is ``models/<model_code>.py``, named by the file's top-level
``model_code`` (``qwen3`` where it names none); it provides
:data:`MODEL_FUNCTIONS`:

* ``shape(config)``: the sizes a run's metrics read (``Run.shape``), with
  ``.vocab`` at least;
* ``program_config(config)``: the program's ``ModelConfig``, checked
  against what the file states;
* ``make_params(shape, seed)``: the weights from the seed, in the
  program's parameter layout (set-up compares it, :func:`check_layout`);
* ``logits_at(params, config, tokens, read, control=False)``: the plain
  float32 reference's logits at positions ``read`` of ``tokens`` (with
  ``control``, the comparison's lower-precision control).
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import time

import numpy as np

import traffic

__all__ = ["Stamped", "Run", "CompileCounter", "load_cell", "load_model",
           "run_cell"]

MODEL_FUNCTIONS = ("shape", "program_config", "make_params", "logits_at")

TRACE_SECONDS = 8.0  # a traced run traces whole rounds until this much time


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class Stamped(list):
    """A request's token list that stamps ``time.perf_counter()`` on each
    append: the moment the loop resolves the token on the host."""

    def __init__(self, *args):
        super().__init__(*args)
        self.times: list[float] = []

    def append(self, tok) -> None:
        self.times.append(time.perf_counter())
        super().append(tok)

    def clear(self) -> None:
        self.times.clear()
        super().clear()


class CompileCounter(contextlib.AbstractContextManager):
    """Counts XLA program builds (compiles and persistent-cache loads), and
    apart from them the jaxpr traces and lowerings to MLIR, which cost host
    time without building a program."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HOST = {"/jax/core/compile/jaxpr_trace_duration": "trace",
            "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower"}

    def __init__(self):
        self.count = 0
        self.secs = 0.0
        self.host = {k: [0, 0.0] for k in self.HOST.values()}

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.secs += duration
        elif event in self.HOST:
            h = self.host[self.HOST[event]]
            h[0] += 1
            h[1] += duration

    def host_snapshot(self) -> dict:
        return {k: tuple(v) for k, v in self.host.items()}

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)
        return False


@dataclasses.dataclass
class Round:
    requests: list  # program Request objects, tokens in Stamped lists
    submit: float  # perf_counter at submission
    end: float  # perf_counter when run() returned
    stats: dict  # the loop's counters for this run() call
    traced: bool = False


@dataclasses.dataclass
class Run:
    """What a metric reader sees of one run."""

    mix: dict
    shape: object  # the model code's shape(config)
    peaks: dict
    chips: int
    setup_s: float
    window_s: float
    rounds: list
    memory_peak: int
    memory_limit: int
    trace: object = None  # tracing.Trace of the traced rounds, or None

    def requests(self, traced_only: bool = False) -> list:
        return [r for rd in self.rounds if rd.traced or not traced_only
                for r in rd.requests]


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_file(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_model(root: str, config: dict):
    """The configuration's model code, ``bench/models/<model_code>.py``."""
    code = config.get("model_code", "qwen3")
    path = os.path.join(root, "bench", "models", f"{code}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"configuration {config.get('name')!r} names "
                                f"model code {code!r}: no file {path}")
    mod = _load_file(path, f"model_{code}")
    missing = [f for f in MODEL_FUNCTIONS if not callable(getattr(mod, f, None))]
    if missing:
        raise AttributeError(f"{path} lacks {missing}")
    return mod


def load_cell(root: str, name: str) -> tuple[dict, dict, dict, dict, object]:
    """(BENCHMARK.json, cell, configuration file, traffic mix, model code)
    of a cell."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = _read_json(os.path.join(root, cfg_entry["file"]))
    mix = traffic.load(cell["traffic"], os.path.join(root, "bench"))
    return bench, cell, config, mix, load_model(root, config)


def cell_metrics(bench: dict, cell: dict, per_layer: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics, or
    with ``per_layer`` its per-layer ones.  A metric without ``workloads``
    belongs to every cell that reports the end-to-end metric it moves."""
    e2e = [m for m in bench["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    if not per_layer:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell["name"] in m.get("workloads", [cell["name"]])
            and m["moves"] in names]


def read_metric(root: str, name: str, run: Run):
    """Load ``bench/metrics/<name>.py`` and call its ``read(run)``."""
    path = os.path.join(root, "bench", "metrics", f"{name}.py")
    return _load_file(path, f"metric_{name}").read(run)


def check_layout(params, abstract) -> None:
    """Raise unless ``params`` has the program's tree, shapes and dtypes."""
    import jax

    def table(tree):
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        return {jax.tree_util.keystr(p): (tuple(a.shape), str(a.dtype))
                for p, a in flat}

    mine, theirs = table(params), table(abstract)
    if mine != theirs:
        diff = sorted(set(mine.items()) ^ set(theirs.items()))
        raise RuntimeError(
            "benchmark weights do not match the program's parameter layout: "
            f"{diff[:6]}")


def use_checkout_cache(root: str) -> None:
    """Keep JAX's persistent compilation cache at ``<root>/.jax_cache`` (a
    fixed path inside the checkout, whatever the machine sets), and cache
    every program, however fast it compiled."""
    import jax

    jax.config.update("jax_compilation_cache_dir", os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def require_chips(n: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform!r} "
                     f"({devs[0].device_kind})")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX found {len(devs)}")
    return devs[:n]


class Session:
    """The system under test, built from the seed: weights, the loop, and
    the round driver.  The mix's ``serving.loop`` object, where it has
    one, adds its keys to the loop's keyword arguments."""

    def __init__(self, config: dict, mix: dict, seed: int, devices, model):
        import jax

        from repro.launch.mesh import make_mesh
        from repro.launch.serving import ServeLoop
        from repro.models import model as M

        self.mix, self.seed = mix, seed
        self.shape = model.shape(config)
        mc = model.program_config(config)
        mesh = make_mesh((1, 1), ("data", "model"), devices=devices[:1])
        self.params = model.make_params(self.shape, seed)
        check_layout(self.params, M.abstract_params(mc))
        sv, s = mix["serving"], config["serving"]
        self.loop = ServeLoop(
            mc, mesh, self.params, batch=sv["batch"],
            cache_len=sv["cache_len"], attn_impl=s["attn_impl"],
            attn_pattern=s["attn_pattern"], chunked=True,
            chunk_size=sv["chunk"], paged=True, pool_pages=sv["pool_pages"],
            kv_dtype=s["kv_dtype"], **sv.get("loop", {}),
        )
        if self.loop.page != s["tile"]:
            raise ValueError(f"pages of {self.loop.page} tokens, the file "
                             f"states {s['tile']}")
        self._uid = 0
        jax.block_until_ready(self.params)

    def requests(self, specs) -> list:
        from repro.launch.serving import Request

        out = []
        for ids, m in specs:
            out.append(Request(uid=self._uid, prompt=ids, max_new=m,
                               generated=Stamped()))
            self._uid += 1
        return out

    def serve(self, specs, traced: bool = False) -> Round:
        from jax.profiler import TraceAnnotation

        with TraceAnnotation("round.submit"):
            reqs = self.requests(specs)
        t0 = time.perf_counter()
        with TraceAnnotation("round.run"):
            self.loop.run(reqs)
        t1 = time.perf_counter()
        with TraceAnnotation("round.collect"):
            stats = {k: v for k, v in self.loop.stats.items()
                     if isinstance(v, (int, float))}
        return Round(reqs, t0, t1, stats, traced)

    def warm_specs(self) -> list[list]:
        """Warm-up rounds: one request per kv_live bucket the mix can reach
        (a prompt one short of the bucket and two new tokens, so its chunk
        and its decode step both land in it), each alone, then one round
        that fills every slot with short requests.  A chunk can end at any
        prompt position (a row that finishes its prompt leaves the rest of
        the step's chunk budget to the next row), so every bucket up to the
        one of the longest request the mix can write is reachable."""
        sv = self.mix["serving"]
        cap = sv["cache_len"]
        top = _bucket(traffic.longest(self.mix), cap)
        buckets = [_bucket(1 << k, cap) for k in range(3, top.bit_length())]
        rng = np.random.default_rng((self.seed, 2))
        vocab = self.shape.vocab
        rounds = [[(rng.integers(0, vocab, b - 1, dtype=np.int32), 2)]
                  for b in sorted(set(buckets))]
        short = min(self.mix["prompt_len"][0], sv["chunk"])
        rounds.append([(rng.integers(0, vocab, short, dtype=np.int32), 3)
                       for _ in range(sv["batch"] + 1)])
        return rounds


def _bucket(n: int, cap: int, floor: int = 8) -> int:
    """The loop's kv_live bucket: the least power of two >= n (>= floor),
    capped at the cache length."""
    b = floor
    while b < n:
        b *= 2
    return min(b, cap)


def served_gaps(model, params, config: dict, requests: list,
                control: bool = False):
    """Per request: the gap by which each served token's reference logit
    (``model.logits_at``) lies below the reference's best at that position
    (for ``control``, the token the control ranks first), and whether the
    tokens agree."""
    out = []
    for r in requests:
        gen = np.asarray(list(r.generated), np.int64)
        seq = np.concatenate([np.asarray(r.prompt, np.int32), gen[:-1].astype(np.int32)])
        read = np.arange(len(r.prompt) - 1, len(seq))
        ref = model.logits_at(params, config, seq, read)
        best = ref.max(-1)
        if control:
            pick = model.logits_at(params, config, seq, read, control=True).argmax(-1)
        else:
            pick = gen
        gap = best - ref[np.arange(len(read)), pick]
        out.append({"uid": r.uid, "tokens": len(read),
                    "finite": bool(np.isfinite(ref).all()),
                    "widest": float(gap.max()),
                    "mismatch": int((pick != ref.argmax(-1)).sum())})
    return out


def verdict(rows: list, failed: int, limit: float) -> bool:
    """The comparison's decision, for a run and for a calibration alike:
    some served tokens were checked, every reference logit is finite, no
    checked token's gap passes ``limit``, and no request fell short."""
    return (bool(rows) and all(r["finite"] for r in rows)
            and max(r["widest"] for r in rows) <= limit and failed == 0)


class HostStalls:
    """Python's garbage-collector pauses, as a ``gc.callbacks`` entry: the
    stderr report tells a host stall by the collector from one outside it."""

    def __init__(self):
        self.count, self.total, self.longest = 0, 0.0, 0.0
        self._t0 = None

    def __call__(self, phase, info) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            d = time.perf_counter() - self._t0
            self.count, self.total = self.count + 1, self.total + d
            self.longest = max(self.longest, d)
            self._t0 = None


def longest_token_gap(requests: list) -> tuple[float, float]:
    """(seconds, when) of the longest time in which no request of the window
    resolved a token: where the host stalled, if it did."""
    times = np.sort(np.concatenate([np.asarray(r.generated.times) for r in requests
                                    if r.generated.times] or [np.zeros(1)]))
    if len(times) < 2:
        return 0.0, 0.0
    i = int(np.argmax(np.diff(times)))
    return float(times[i + 1] - times[i]), float(times[i])


def check_sample(requests: list, k: int, seed: int) -> list:
    """``k`` finished requests drawn from the seed, the longest among them."""
    done = [r for r in requests if len(r.generated) == r.max_new]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + r.max_new, -r.uid))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng((seed, 3))
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def run_cell(root: str, workload: str, seed: int, seconds: float, trace: bool,
             t_start: float) -> dict:
    """One run; returns the result object (its ``correct`` says whether the
    served tokens passed the comparison)."""
    bench, cell, config, mix, model = load_cell(root, workload)
    import jax

    devices = require_chips(cell["chips"])
    peaks_all = _read_json(os.path.join(root, "bench", "peaks.json"))
    kind = devices[0].device_kind
    if kind not in peaks_all:
        raise RuntimeError(f"device kind {kind!r} has no peaks in bench/peaks.json")
    peaks = peaks_all[kind]
    use_checkout_cache(root)
    limits = _read_json(os.path.join(root, "bench", "limits", f"{workload}.json"))

    with CompileCounter() as builds:
        sess = Session(config, mix, seed, devices, model)
        for specs in sess.warm_specs():
            sess.serve(specs)
        # what set-up made lives to the end: keep it out of the collector's
        # full passes, which otherwise walk every object of the imports and
        # compiled programs and stall the host for up to seconds at random
        gc.collect()
        gc.freeze()
        warm_builds = builds.count
        warm_host = builds.host_snapshot()
        setup_s = time.perf_counter() - t_start
        print(f"[{workload}] setup {setup_s:.3f} s, {warm_builds} program builds "
              f"({builds.secs:.1f} s)", file=sys.stderr, flush=True)

        rounds: list[Round] = []
        stalls = HostStalls()
        gc.callbacks.append(stalls)
        tdir = os.path.join(root, ".bench_trace", workload)
        if trace:
            shutil.rmtree(tdir, ignore_errors=True)
            # device ops and the harness's spans only: the Python tracer
            # would record every call of the loop's host code and slow it
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(tdir, profiler_options=opts)
        t_window = None
        index = 0
        while True:
            rd = sess.serve(traffic.make_round(mix, seed, index, sess.shape.vocab,
                                               stream=1), traced=trace)
            rounds.append(rd)
            index += 1
            if t_window is None:
                t_window = rd.submit
            elapsed = rd.end - t_window
            if trace:
                # a traced run's window is whole rounds until TRACE_SECONDS
                if elapsed >= min(seconds, TRACE_SECONDS):
                    break
            # stop where one more round would end nearer past --seconds
            # than this one ends short of it: every seed's rounds hold the
            # same work, so the count of rounds does not swing by seed
            elif elapsed + 0.5 * (rd.end - rd.submit) >= seconds:
                break
        if trace:
            jax.profiler.stop_trace()
        window_s = rounds[-1].end - t_window
        gc.callbacks.remove(stalls)
        in_window = builds.count - warm_builds
        host_work = {k: (n - warm_host[k][0], t - warm_host[k][1])
                     for k, (n, t) in builds.host_snapshot().items()}
    gc.unfreeze()
    if in_window:
        print(f"[{workload}] {in_window} program builds inside the measured "
              "window", file=sys.stderr, flush=True)
        raise SystemExit(3)

    stats = [d.memory_stats() or {} for d in devices]
    mem_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    mem_limit = min(s.get("bytes_limit", 0) for s in stats) or int(peaks["hbm_bytes"])
    tr = None
    if trace:
        import tracing

        tr = tracing.read(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        traced = [rd for rd in rounds if rd.traced]
        tracing.check_complete(tr, traced[-1].end - traced[0].submit)
    run = Run(mix=mix, shape=sess.shape, peaks=peaks,
              chips=len(devices), setup_s=setup_s, window_s=window_s,
              rounds=rounds, memory_peak=mem_peak, memory_limit=mem_limit,
              trace=tr)
    metrics = {}
    for m in cell_metrics(bench, cell, per_layer=trace):
        v = read_metric(root, m["name"], run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(mem_peak)}
    result = {"correct": False, "attempted": 0, "failed": 0,
              "metrics": metrics, "device": device}
    window_reqs = run.requests()
    result["attempted"] = len(window_reqs)
    result["failed"] = sum(len(r.generated) != r.max_new for r in window_reqs)
    if tr is not None:
        import tracing

        lo, hi = tr.window()
        evs = list(tr.devices.values())
        busy = [tracing.busy_ns(e, lo, hi) for e in evs]
        device["busy_s"] = float(np.mean(busy)) * 1e-9 if busy else 0.0
        device["window_s"] = (hi - lo) * 1e-9
        if evs:
            result["breakdown"] = {
                "device_ops": tracing.op_totals(evs[0], lo, hi),
                "idle_gaps": tracing.idle_gaps(evs[0], tr.host, lo, hi),
            }
    st = {k: sum(rd.stats.get(k, 0) for rd in rounds)
          for k in ("chunk_calls", "decode_steps", "decode_tokens",
                    "prefill_tokens", "admission_backpressure", "preemptions")}
    print(f"[{workload}] window {window_s:.3f} s, {len(rounds)} rounds "
          f"({', '.join(f'{rd.end - rd.submit:.3f}' for rd in rounds)} s), "
          f"{result['attempted']} requests, counters {st}", file=sys.stderr)
    gap, when = longest_token_gap(window_reqs)
    print(f"[{workload}] longest time with no token resolved {gap:.3f} s, "
          f"{when - t_window:.1f} s into the window; garbage collector "
          f"{stalls.count} passes, {stalls.total:.3f} s, longest "
          f"{stalls.longest:.3f} s; in the window "
          + ", ".join(f"{n} jaxpr {k}s {t:.3f} s" for k, (n, t) in host_work.items()),
          file=sys.stderr, flush=True)

    # the comparison: the window's program state goes first, so the
    # reference never sets the process's memory peak
    params, sample = sess.params, check_sample(window_reqs, mix["check_requests"], seed)
    del sess, run, rounds
    gc.collect()
    t0 = time.perf_counter()
    rows = served_gaps(model, params, config, sample)
    widest = max((r["widest"] for r in rows), default=float("inf"))
    limit = float(limits["widest_logit_gap"]["limit"])
    result["correct"] = verdict(rows, result["failed"], limit)
    for r in rows:
        print(f"[{workload}] check uid {r['uid']}: {r['tokens']} served tokens, "
              f"widest gap {r['widest']:.6f}, {r['mismatch']} differ from the "
              "reference's best", file=sys.stderr)
    print(f"[{workload}] reference {time.perf_counter() - t0:.1f} s", file=sys.stderr)
    served = sum(r["tokens"] for r in rows)
    result["checks"] = {
        "widest_logit_gap": {"value": widest, "limit": limit},
        "failed_requests": {"value": result["failed"], "limit": 0},
    }
    print(f"widest_logit_gap {widest!r} limit {limit!r} over {served} served tokens",
          file=sys.stderr)
    print(f"failed_requests {result['failed']} limit 0", file=sys.stderr, flush=True)
    return result
