"""The trace reduction on synthetic events, and the reader on a small trace
recorded here (host spans only: the CPU has no device plane)."""

import jax
import jax.numpy as jnp
import pytest

import tracing
from tracing import Event


def _ev(name, a, b):
    return Event(name, float(a), float(b - a))


OPS = [
    _ev("%fusion.1 = bf16[4,8]{1,0} fusion(bf16[4,8]{1,0} %p), kind=kLoop", 10, 30),
    _ev("%mha_chunk_paged.2 = bf16[1,8]{1,0} custom-call(s32[4]{0} %a)", 30, 40),
    _ev("%fusion.1 = bf16[4,8]{1,0} fusion(bf16[4,8]{1,0} %p), kind=kLoop", 60, 70),
    _ev("%mha_decode_paged.13 = bf16[4,8]{1,0} custom-call(s32[4]{0} %b)", 75, 80),
    _ev("%copy.3 = bf16[28,64]{1,0:T(8,128)} copy(bf16[28,64]{1,0} %c)", 120, 150),
]
HOST = [_ev("round.submit", 0, 12), _ev("round.run", 12, 110),
        _ev("round.collect", 110, 130)]


def test_busy_union_and_idle_share():
    assert tracing.busy_ns(OPS, 0, 130) == (40 - 10) + (70 - 60) + (80 - 75) + (130 - 120)
    assert tracing.busy_ns(OPS, 26, 62) == (40 - 26) + (62 - 60)
    assert tracing.busy_ns([], 0, 10) == 0


def test_idle_gaps_are_named_by_the_covering_span():
    gaps = tracing.idle_gaps(OPS, HOST, 0, 130)
    assert gaps[0] == ["round.run", pytest.approx(40e-9)]  # 80..120, mostly run
    assert ["round.submit", pytest.approx(10e-9)] in gaps  # 0..10
    assert sum(g[1] for g in gaps) == pytest.approx((130 - 55) * 1e-9)


def test_kernel_time_matches_op_names():
    ns, n = tracing.match_ns(OPS, ("mha_chunk_paged", "mha_decode_paged"), 0, 200)
    assert (ns, n) == (10 + 5, 2)
    assert tracing.match_ns(OPS, ("mha_chunk",), 0, 200) == (0.0, 0)
    assert tracing.match_ns(OPS, ("fusion",), 0, 50) == (20.0, 1)


def test_op_totals_rank_by_self_time():
    nested = OPS + [_ev("%while.7 = (s32[], bf16[4]{0}) while((s32[], bf16[4]{0}) %t), "
                        "condition=%c, body=%b", 5, 45)]
    top = tracing.op_totals(nested, 0, 200)
    assert sorted(top[:2]) == [["%copy.3 = bf16[28,64] copy", pytest.approx(30e-9)],
                               ["%fusion.1 = bf16[4,8] fusion", pytest.approx(30e-9)]]
    assert ["%while.7 = (...) while", pytest.approx((40 - 20 - 10) * 1e-9)] in top
    assert ["%mha_chunk_paged.2 = bf16[1,8] custom-call", pytest.approx(10e-9)] in top


def test_read_a_recorded_trace(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for name in tracing.HOST_SPANS:
        with jax.profiler.TraceAnnotation(name):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = tracing.read(str(tmp_path))
    assert [e.name for e in tr.host] == list(tracing.HOST_SPANS)
    lo, hi = tr.window()
    assert hi > lo


def test_an_incomplete_trace_is_refused():
    tr = tracing.Trace({"/device:TPU:0": OPS}, HOST)
    tracing.check_complete(tr, 130e-9)  # ops run to 150 > 130
    with pytest.raises(RuntimeError, match="harness spans"):
        tracing.check_complete(tr, 200e-9)
    cut = tracing.Trace({"/device:TPU:0": OPS[:3]}, HOST)  # ops stop at 70
    with pytest.raises(RuntimeError, match="device ops stop"):
        tracing.check_complete(cut, 130e-9)
