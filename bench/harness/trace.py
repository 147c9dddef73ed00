"""Reduction of a profiler trace to device busy time and kernel time.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it. Each chip is a plane named ``/device:TPU:<n>``; its line
``XLA Ops`` holds one event per operation the chip ran, with the
operation's name (a Pallas kernel carries its kernel's name) and its
start and duration in nanoseconds. The host plane holds the benchmark's
``bench.<span>`` annotations on the same clock.

* window: the span between the ``bench.trace_start`` and
  ``bench.trace_stop`` markers the tracer writes on the host plane; op
  intervals are clipped to it;
* busy: the union of the op intervals on each device plane, averaged over
  the chips the cell uses; idle share = 1 - busy / window;
* kernel time: the summed durations of the ops whose name contains the
  kernel's name (an op's name is its HLO text: ``%_greedy_round.1 =
  (...) custom-call(f32[48128,512] %pad.0, ...)``, operand shapes
  included);
* idle gaps: the spaces between op intervals on the first chip (and the
  window's edges), each named by the benchmark span that was open at its
  middle, summed per name.
"""
from __future__ import annotations

import dataclasses
import glob
import math
import os
import re
import shutil
import threading
import time
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
MARK_START = "bench.trace_start"
MARK_STOP = "bench.trace_stop"
# ops whose events are kept for per-call work accounting: the greedy round
# kernel, and the pads that show how many rows a padded operand really has
KEEP_EVENTS = ("_greedy_round", "%pad")


@dataclasses.dataclass
class Reduction:
    chips: int
    window_s: float
    busy_s: float                             # mean over the chips used
    op_s: Dict[str, float]                    # op name -> summed seconds
    op_count: Dict[str, int]
    op_events: Dict[str, List[Tuple[int, int, dict]]]
    idle_gaps: List[Tuple[str, float]]        # summed per host span

    def kernel_s(self, name: str) -> float:
        return sum(s for op, s in self.op_s.items() if name in op)

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """The ``n`` op kinds (HLO op names without their numeric suffix)
        that took the most device time."""
        kinds: Dict[str, float] = defaultdict(float)
        for op, sec in self.op_s.items():
            kinds[op_kind(op)] += sec
        return sorted(kinds.items(), key=lambda kv: -kv[1])[:n]


def op_kind(name: str) -> str:
    """``%fusion.97 = (f32[...]) fusion(...)`` -> ``fusion``."""
    head = name.split(" = ", 1)[0].lstrip("%").strip()
    return re.sub(r"\.\d+$", "", head) or name[:64]


class Tracer:
    """The profiler over the last stretch of a window: ``arm(at)`` starts
    it at host clock ``at`` (at once if that has passed), ``stop`` ends it
    after the window. Markers ``bench.trace_start`` and
    ``bench.trace_stop`` put the traced span on the trace's own clock.
    A stretch, not the whole window: a busy cell's whole window can hold
    more events than the host's memory, and the untraced part keeps the
    host-clock per-layer metrics free of the profiler's cost."""

    def __init__(self, root: Path, cell: str):
        self.out = root / "bench" / ".cache" / "trace" / cell
        self._lock = threading.Lock()
        self._timer = None
        self.started_at = math.inf
        self._stopped = False

    def arm(self, at: float):
        shutil.rmtree(self.out, ignore_errors=True)
        self.out.mkdir(parents=True)
        self._timer = threading.Timer(max(at - time.perf_counter(), 0.0),
                                      self._start)
        self._timer.start()

    def _start(self):
        import jax
        with self._lock:
            if self._stopped or self.started_at < math.inf:
                return
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(self.out), profiler_options=opts)
            self.started_at = time.perf_counter()
            with jax.profiler.TraceAnnotation(MARK_START):
                pass

    def stop(self, chips: int) -> "Reduction":
        """End the trace (starting it first if the window was shorter than
        the stretch) and reduce it."""
        import jax
        self._timer.cancel()
        self._start()
        with self._lock:
            with jax.profiler.TraceAnnotation(MARK_STOP):
                pass
            jax.profiler.stop_trace()
            self._stopped = True
        return reduce_profile(load(str(self.out)), chips)

    def cancel(self):
        """Stop the timer and, if the trace is still on, the trace."""
        import jax
        if self._timer is not None:
            self._timer.cancel()
        with self._lock:
            if self.started_at < math.inf and not self._stopped:
                jax.profiler.stop_trace()
            self._stopped = True


def _union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _stats(ev) -> dict:
    try:
        return {k: v for k, v in ev.stats}
    except (TypeError, ValueError):
        return {}


def load(path: str):
    import jax
    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return jax.profiler.ProfileData.from_file(files[-1])


def _markers(profile) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for p in profile.planes:
        if p.name.startswith(DEVICE_PREFIX):
            continue
        for line in p.lines:
            for ev in line.events:
                if ev.name in (MARK_START, MARK_STOP):
                    out.setdefault(ev.name, int(ev.start_ns))
    return out


def reduce_profile(profile, chips: int, window_s: Optional[float] = None,
                   keep_events: Sequence[str] = KEEP_EVENTS) -> Reduction:
    """Reduce the traced span between the ``bench.trace_start`` and
    ``bench.trace_stop`` markers: op intervals are clipped to it, and the
    events kept for per-call work accounting (op names containing one of
    ``keep_events``) are those wholly inside it. A trace without the
    markers is taken whole, over ``window_s``."""
    marks = _markers(profile)
    if MARK_START in marks and MARK_STOP in marks:
        lo, hi = marks[MARK_START], marks[MARK_STOP]
        window_s = (hi - lo) * 1e-9
    elif window_s is None:
        raise ValueError("a trace without markers needs its window_s")
    else:
        lo, hi = -math.inf, math.inf
    device_planes = sorted(
        (p for p in profile.planes if p.name.startswith(DEVICE_PREFIX)),
        key=lambda p: int(p.name[len(DEVICE_PREFIX):]))[:chips]
    spans: List[Tuple[int, int, str]] = []
    for p in profile.planes:
        if p.name.startswith(DEVICE_PREFIX):
            continue
        for line in p.lines:
            for ev in line.events:
                if (ev.name.startswith(SPAN_PREFIX)
                        and ev.name not in (MARK_START, MARK_STOP)):
                    s = int(ev.start_ns)
                    spans.append((s, s + int(ev.duration_ns),
                                  ev.name[len(SPAN_PREFIX):]))
    op_s: Dict[str, float] = defaultdict(float)
    op_count: Dict[str, int] = defaultdict(int)
    op_events: Dict[str, List[Tuple[int, int, dict]]] = defaultdict(list)
    busy = []
    first_busy: List[Tuple[int, int]] = []
    for i, plane in enumerate(device_planes):
        ivs = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s, d = int(ev.start_ns), int(ev.duration_ns)
                cs, ce = max(s, lo), min(s + d, hi)
                if ce <= cs:
                    continue
                ivs.append((cs, ce))
                op_s[ev.name] += (ce - cs) * 1e-9
                op_count[ev.name] += 1
                if (cs, ce) == (s, s + d) and any(k in ev.name
                                                  for k in keep_events):
                    op_events[ev.name].append((s, d, _stats(ev)))
        merged = _union(ivs)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        if i == 0:
            first_busy = merged
    edges = []
    if first_busy and math.isfinite(lo):
        edges = [(lo, lo)] + first_busy + [(hi, hi)]
    else:
        edges = first_busy
    gaps: Dict[str, float] = defaultdict(float)
    for (_, e0), (s1, _) in zip(edges, edges[1:]):
        if s1 <= e0:
            continue
        mid = (e0 + s1) // 2
        open_spans = [name for s, e, name in spans if s <= mid < e]
        gaps[open_spans[-1] if open_spans else "other"] += (s1 - e0) * 1e-9
    chips_seen = max(len(device_planes), 1)
    return Reduction(chips=len(device_planes), window_s=window_s,
                     busy_s=sum(busy) / chips_seen, op_s=dict(op_s),
                     op_count=dict(op_count), op_events=dict(op_events),
                     idle_gaps=sorted(gaps.items(), key=lambda g: -g[1]))
