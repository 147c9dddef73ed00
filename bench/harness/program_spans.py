"""Readings of the program's own spans and counters
(``repro.common.telemetry``, in the server's process, which is the
benchmark's).

Host clock: the recorder's ring holds each span (``alaas.<name>``) and
each counter increment, stamped with ``time.perf_counter``, the clock of
``run.window`` and ``run.traced_from``. ``events(run, end)`` takes those
that ended inside the window and no later than ``end``. It gives None for
a program without the recorder, and when the ring dropped an event of
that stretch.

Device trace: a ``--trace 1`` run leaves its ``.xplane.pb`` in the
tracer's directory (``bench/.cache/trace/<cell>``). There the spans are
``alaas.`` annotations on the host plane, on the clock of the chips' ``XLA
Ops``. ``stretch(run)`` clips both to the ``bench.trace_start`` /
``bench.trace_stop`` markers; ``Stretch.idle_share_under`` is the first
chip's idle share under one span name, and ``Stretch.idle_by_span`` names
each idle stretch by the innermost program span open over it.

    python3 -m bench.harness.program_spans bench/.cache/trace/<cell>

prints that breakdown of a run's trace.
"""
from __future__ import annotations

import bisect
import dataclasses
import json
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from bench.harness import trace

PREFIX = "alaas."
OTHER = "other"


# ------------------------------------------------------------ host clock --
def recorder():
    """The program's recorder module, or None where it has none."""
    try:
        from repro.common import telemetry
    except ImportError:
        return None
    return telemetry


def events(run, end: Optional[float] = None) -> Optional[list]:
    """The recorder's events that ended inside the window and no later
    than ``end``; None without a recorder or when the ring dropped an
    event of that stretch (its oldest kept event ended inside it)."""
    tel = recorder()
    if tel is None:
        return None
    lo, hi = run.window
    if end is not None:
        hi = min(hi, end)
    evs = tel.events()
    dropped = tel.snapshot()["counters"].get("trace.dropped", 0)
    if dropped and (not evs or evs[0].t1 >= lo):
        return None
    return [e for e in evs if lo <= e.t1 <= hi]


def span_s(evs, name: str) -> float:
    """Summed seconds of the spans ``name``."""
    return sum(e.t1 - e.t0 for e in evs
               if e.name == name and e.value is None)


def span_count(evs, name: str) -> int:
    return sum(1 for e in evs if e.name == name and e.value is None)


def counted(evs, name: str) -> float:
    """Summed increments of the counter ``name``."""
    return sum(e.value for e in evs
               if e.name == name and e.value is not None)


def per_untraced_round(run, value):
    """``value(events)`` over the rounds that ended before the profiler
    started, per round."""
    from bench.harness import readers
    rounds = readers.untraced_rounds(run)
    if not rounds:
        return None
    evs = events(run, rounds[-1][1])
    if evs is None:
        return None
    return value(evs) / len(rounds)


def per_1000_untraced_rows(run, name: str):
    """Seconds in the span ``name`` per 1,000 rows acknowledged before the
    profiler started."""
    end = min(run.traced_from, run.window[1])
    rows = sum(n for t, n in getattr(run, "push_log", None) or []
               if t <= end)
    evs = events(run, end)
    if not rows or evs is None or not span_count(evs, name):
        return None
    return span_s(evs, name) / (rows / 1000.0)


# ---------------------------------------------------------- device trace --
@dataclasses.dataclass
class Stretch:
    """The traced stretch: the first chip's busy intervals and the
    program's spans, in nanoseconds on the trace's clock."""
    lo: int
    hi: int
    busy: List[Tuple[int, int]]                  # merged, sorted
    spans: List[Tuple[int, int, str]]            # clipped, by start

    def __post_init__(self):
        total, self._cum = 0, []
        for s, e in self.busy:
            self._cum.append(total)
            total += e - s
        self._starts = [s for s, _ in self.busy]

    def busy_before(self, t: int) -> int:
        """Busy nanoseconds of the first chip before ``t``."""
        i = bisect.bisect_right(self._starts, t) - 1
        if i < 0:
            return 0
        s, e = self.busy[i]
        return self._cum[i] + min(t, e) - s

    def idle_ns(self, a: int, b: int) -> int:
        return (b - a) - (self.busy_before(b) - self.busy_before(a))

    def idle_share_under(self, name: str) -> Optional[float]:
        """Idle share of the first chip under the union of the spans
        ``name``; None where no such span lies in the stretch."""
        merged = trace._union([(s, e) for s, e, n in self.spans
                               if n == name])
        length = sum(e - s for s, e in merged)
        if length <= 0:
            return None
        return sum(self.idle_ns(s, e) for s, e in merged) / length

    def idle_by_span(self) -> Dict[str, float]:
        """{innermost open span or ``other``: idle seconds of the first
        chip}. Between two consecutive span edges the open spans are
        fixed; the innermost is the one that opened last."""
        edges = sorted({self.lo, self.hi}
                       | {t for s, e, _ in self.spans for t in (s, e)})
        by_end = sorted(range(len(self.spans)),
                        key=lambda i: self.spans[i][1])
        out: Dict[str, float] = defaultdict(float)
        active: Dict[int, Tuple[int, str]] = {}
        si = ei = 0
        for a, b in zip(edges, edges[1:]):
            while si < len(self.spans) and self.spans[si][0] <= a:
                active[si] = (self.spans[si][0], self.spans[si][2])
                si += 1
            while ei < len(by_end) and self.spans[by_end[ei]][1] <= a:
                active.pop(by_end[ei], None)
                ei += 1
            idle = self.idle_ns(a, b)
            if idle > 0:
                name = max(active.values())[1] if active else OTHER
                out[name] += idle * 1e-9
        return dict(out)


def stretch_of(profile) -> Optional[Stretch]:
    """The first chip's busy intervals and the ``alaas.`` spans between
    the tracer's markers; None without the markers."""
    marks = trace._markers(profile)
    if trace.MARK_START not in marks or trace.MARK_STOP not in marks:
        return None
    lo, hi = marks[trace.MARK_START], marks[trace.MARK_STOP]
    devices = sorted(
        (p for p in profile.planes if p.name.startswith(trace.DEVICE_PREFIX)),
        key=lambda p: int(p.name[len(trace.DEVICE_PREFIX):]))
    ops = []
    for line in (devices[0].lines if devices else []):
        if line.name != trace.OPS_LINE:
            continue
        for ev in line.events:
            s = int(ev.start_ns)
            cs, ce = max(s, lo), min(s + int(ev.duration_ns), hi)
            if ce > cs:
                ops.append((cs, ce))
    spans = []
    for p in profile.planes:
        if p.name.startswith(trace.DEVICE_PREFIX):
            continue
        for line in p.lines:
            for ev in line.events:
                if not ev.name.startswith(PREFIX):
                    continue
                s = int(ev.start_ns)
                cs, ce = max(s, lo), min(s + int(ev.duration_ns), hi)
                if ce > cs:
                    spans.append((cs, ce, ev.name))
    spans.sort()
    return Stretch(lo=lo, hi=hi, busy=trace._union(ops), spans=spans)


def stretch(run) -> Optional[Stretch]:
    """The traced stretch of a ``--trace 1`` run (read once per run);
    None for an untraced run or one whose trace has no markers."""
    if run.trace_reduction is None:
        return None
    if not hasattr(run, "program_stretch"):
        path = run.spec.root / "bench" / ".cache" / "trace" / run.spec.name
        try:
            run.program_stretch = stretch_of(trace.load(str(path)))
        except FileNotFoundError:
            run.program_stretch = None
    return run.program_stretch


def main(argv=None) -> int:
    path = (argv or sys.argv[1:])[0]
    st = stretch_of(trace.load(path))
    if st is None:
        print(f"no trace markers in {path}", file=sys.stderr)
        return 1
    idle = st.idle_by_span()
    total = sum(idle.values())
    out = {"window_s": (st.hi - st.lo) * 1e-9, "idle_s": total,
           "covered": 1.0 - idle.get(OTHER, 0.0) / total if total else None,
           "idle_by_span": sorted(idle.items(), key=lambda kv: -kv[1])}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
