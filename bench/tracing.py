"""Reduction of a profiler trace to device busy time, idle gaps and kernel
time.

A trace is read into two lists: the device operations (one list per
device, from the ``XLA Ops`` line of each ``/device:`` plane) and the
harness's own host spans (``round.submit``, ``round.run``,
``round.collect``, written with ``jax.profiler.TraceAnnotation``).  Times
are nanoseconds on the profiler's one clock.  The functions below work on
those lists, so they can be checked on synthetic events.
"""

from __future__ import annotations

import dataclasses
import glob
import os

__all__ = ["Event", "Trace", "read", "check_complete", "busy_ns", "idle_gaps",
           "op_totals", "match_ns", "short_name"]

HOST_SPANS = ("round.submit", "round.run", "round.collect")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str  # the op's HLO text on a device, the span's name on the host
    start: float  # ns
    dur: float  # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    devices: dict[str, list[Event]]  # plane name -> device ops
    host: list[Event]  # harness spans

    def window(self) -> tuple[float, float]:
        """From the first harness span's start to the last one's end."""
        if not self.host:
            raise ValueError("the trace holds no harness spans")
        return min(e.start for e in self.host), max(e.end for e in self.host)


def read(log_dir: str) -> Trace:
    """Load the newest ``*.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no xplane trace under {log_dir}")
    pd = ProfileData.from_file(files[-1])
    devices: dict[str, list[Event]] = {}
    host: list[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                ops.extend(Event(e.name, float(e.start_ns), float(e.duration_ns))
                           for e in line.events)
            if ops:
                devices[plane.name] = sorted(ops, key=lambda e: e.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        host.append(Event(e.name, float(e.start_ns),
                                          float(e.duration_ns)))
    return Trace(devices, sorted(host, key=lambda e: e.start))


def check_complete(tr: Trace, host_s: float) -> None:
    """Raise unless the trace covers the traced rounds: its harness spans
    span at least the rounds' host-clock length, and every device's ops run
    on to the end of them (a profiler that drops events once its buffer is
    full would otherwise pass a part of the work off as all of it)."""
    lo, hi = tr.window()
    if hi - lo < 0.99 * host_s * 1e9:
        raise RuntimeError(f"trace holds {(hi - lo) * 1e-9:.3f} s of harness "
                           f"spans for {host_s:.3f} s of traced rounds")
    for name, ev in tr.devices.items():
        last = max((e.end for e in ev if e.start < hi), default=lo)
        if last < hi - 0.02 * (hi - lo):
            raise RuntimeError(f"{name}: device ops stop {(hi - last) * 1e-9:.3f} s "
                               "before the traced rounds end")


def _merged(events: list[Event], lo: float, hi: float) -> list[tuple[float, float]]:
    """Union of the events' intervals, clipped to [lo, hi]."""
    out: list[list[float]] = []
    for e in sorted(events, key=lambda e: e.start):
        a, b = max(e.start, lo), min(e.end, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(events: list[Event], lo: float, hi: float) -> float:
    """Time in [lo, hi] during which at least one operation ran."""
    return sum(b - a for a, b in _merged(events, lo, hi))


def idle_gaps(events: list[Event], host: list[Event], lo: float, hi: float,
              top: int = 10) -> list[list]:
    """The longest stretches of [lo, hi] with no device operation, each
    named by the harness span that covers most of it (``outside`` where
    none does), longest first: ``[[name, seconds], ...]``."""
    gaps, t = [], lo
    for a, b in _merged(events, lo, hi):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    named = []
    for a, b in gaps:
        best, cover = "outside", 0.0
        for s in host:
            c = min(b, s.end) - max(a, s.start)
            if c > cover:
                best, cover = s.name, c
        named.append([best, (b - a) * 1e-9])
    named.sort(key=lambda x: -x[1])
    return named[:top]


def short_name(hlo: str) -> str:
    """``%name = type opcode`` of an op's HLO text, without layouts and
    operands; a tuple type shows as ``(...)``."""
    lhs, sep, rhs = hlo.partition(" = ")
    if not sep:
        return hlo[:120]
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        typ, rest = "(...)", rhs[i + 1:].lstrip()
    else:
        typ, _, rest = rhs.partition(" ")
        typ = typ.split("{")[0]
    return f"{lhs} = {typ} {rest.split('(')[0]}"


def op_totals(events: list[Event], lo: float, hi: float,
              top: int = 10) -> list[list]:
    """Device self time per operation inside [lo, hi], largest first, by
    :func:`short_name`.  Ops nest (a ``while`` holds its body's ops), so an
    op's time excludes the ops that run inside it."""
    inside = sorted((e for e in events if lo <= e.start < hi),
                    key=lambda e: (e.start, -e.dur))
    self_ns = [e.dur for e in inside]
    stack: list[int] = []
    for i, e in enumerate(inside):
        while stack and inside[stack[-1]].end <= e.start:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= e.dur
        stack.append(i)
    tot: dict[str, float] = {}
    for e, ns in zip(inside, self_ns):
        name = short_name(e.name)
        tot[name] = tot.get(name, 0.0) + ns
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns * 1e-9] for name, ns in ranked]


def match_ns(events: list[Event], names: tuple[str, ...], lo: float,
             hi: float) -> tuple[float, int]:
    """Summed duration and count of the events inside [lo, hi] whose op
    name (before ``=``, numbering dropped) is one of ``names``."""
    total, n = 0.0, 0
    for e in events:
        if lo <= e.start < hi and _op(e.name) in names:
            total += e.dur
            n += 1
    return total, n


def _op(hlo: str) -> str:
    """``%mha_decode_paged.13 = ...`` -> ``mha_decode_paged``."""
    head = hlo.partition(" = ")[0].lstrip("%")
    base, dot, num = head.rpartition(".")
    return base if dot and num.isdigit() else head
