"""Host phases of the paged chunked engine: the ``serve.*`` profiler spans,
the ``host_*`` self-time counters in ``loop.stats`` and the ``admitted``
stamp on each request.

Only invariants are checked (no timing thresholds): self times are
non-negative and fit inside the ``run()`` call, each phase's longest self
time is at most its sum, the stamp lies between submission and the first
resolved token, and in a profiler trace every phase span sits inside a
step span."""

import dataclasses
import glob
import os
import time

import jax
import numpy as np
import pytest

from repro.configs import registry
from repro.launch.mesh import make_local_mesh
from repro.launch.serve import Request, ServeLoop
from repro.models import model as M

PHASES = ("step", "admit", "decode", "chunk", "resolve")
LENS = [(40, 4), (23, 3), (57, 5), (9, 2), (33, 3)]  # (prompt, max_new)


class _Stamped(list):
    """Token list stamping ``time.perf_counter()`` as each token resolves."""

    def __init__(self):
        super().__init__()
        self.times: list[float] = []

    def append(self, tok) -> None:
        self.times.append(time.perf_counter())
        super().append(tok)

    def clear(self) -> None:
        self.times.clear()
        super().clear()


def _loop(**kw):
    cfg = dataclasses.replace(registry.get("qwen3-0.6b", reduced=True),
                              dtype="float32")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return ServeLoop(cfg, make_local_mesh(), params, batch=2, cache_len=128,
                     attn_impl="xla_chunked", chunk_size=16, **kw)


def _requests(vocab: int) -> list[Request]:
    rng = np.random.default_rng(5)
    return [Request(uid=i, prompt=rng.integers(0, vocab, ln).astype(np.int32),
                    max_new=mn, generated=_Stamped())
            for i, (ln, mn) in enumerate(LENS)]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One untimed-profiler run and one traced run of the same requests."""
    loop = _loop(chunked=True, paged=True)
    reqs = _requests(loop.cfg.vocab)
    loop.run(reqs)  # builds every program the runs below use
    t0 = time.perf_counter()
    loop.run(reqs)
    t1 = time.perf_counter()
    stats = dict(loop.stats)
    stamps = [(r.admitted, list(r.generated.times)) for r in reqs]
    tdir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(tdir)
    loop.run(reqs)
    jax.profiler.stop_trace()
    return {"loop": loop, "reqs": reqs, "submit": t0, "end": t1,
            "stats": stats, "stamps": stamps, "events": _host_events(tdir)}


def _host_events(tdir: str) -> list[tuple[str, float, float, dict]]:
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(tdir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return out


@pytest.mark.parametrize("phase", PHASES)
def test_phase_counters(served, phase):
    """Each phase's self time is a float >= 0; but for resolve its longest
    single self time lies between 0 and the sum."""
    st = served["stats"]
    total = st[f"host_{phase}_s"]
    assert isinstance(total, float) and total >= 0.0
    longest = st.get(f"host_{phase}_max_s")
    if phase == "resolve":
        assert longest is None
    else:
        assert isinstance(longest, float) and 0.0 <= longest <= total
    if phase in ("step", "decode", "chunk", "resolve"):
        assert total > 0.0, f"the run never entered {phase}"


def test_self_times_fit_inside_the_run(served):
    st = served["stats"]
    assert sum(st[f"host_{p}_s"] for p in PHASES) <= served["end"] - served["submit"]


def test_admitted_between_submit_and_first_token(served):
    for admitted, times in served["stamps"]:
        assert admitted is not None and times
        assert served["submit"] <= admitted <= times[0]


def test_second_run_resets_the_stamp(served):
    """The traced (third) run stamped every request anew, after the second
    run ended."""
    for r, (before, _) in zip(served["reqs"], served["stamps"]):
        assert r.admitted > served["end"] > before


@pytest.mark.parametrize("phase", PHASES)
def test_trace_holds_phase_spans(served, phase):
    assert any(name == f"serve.{phase}" for name, *_ in served["events"])


def test_phase_spans_nest_in_step_spans(served):
    steps = [(a, b) for name, a, b, _ in served["events"] if name == "serve.step"]
    inner = [(a, b) for name, a, b, _ in served["events"]
             if name in ("serve.admit", "serve.decode", "serve.chunk")]
    assert steps and inner
    for a, b in inner:
        assert any(sa <= a and b <= sb for sa, sb in steps), (a, b)


def test_span_arguments(served):
    """Chunk spans carry the request's uid, so one request's spans share
    it; step spans carry their engine step."""
    events = served["events"]
    chunks = [st for name, _, _, st in events if name == "serve.chunk"]
    assert {st["req"] for st in chunks} == {r.uid for r in served["reqs"]}
    assert all(st["tokens"] >= 1 for st in chunks)
    steps = [st["step_num"] for name, _, _, st in events if name == "serve.step"]
    assert steps == sorted(steps) and steps[0] == 0


@pytest.mark.parametrize("mode", [dict(chunked=True), dict(paged=True), {}])
def test_other_engines_stamp_admission_without_phases(mode):
    """The parity engines stamp ``admitted`` too, and keep no phase
    counters."""
    loop = _loop(**mode)
    reqs = _requests(loop.cfg.vocab)[:3]
    t0 = time.perf_counter()
    loop.run(reqs)
    assert all(t0 <= r.admitted <= r.generated.times[0] for r in reqs)
    assert not any(k.startswith("host_") for k in loop.stats)
