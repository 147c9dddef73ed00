"""Per-chip readings of the pick loop in a ``--trace 1`` run's device trace.

Under each ``alaas.select.greedy`` annotation (the program's pick loop, on
the host plane), per device plane of the cell's chips:

* ``busy_s``: the union of the chip's ``XLA Ops`` intervals;
* ``merge_s``: the union of its collective ops' intervals. The loop's
  only collective is its cross-chip merge. The program's device scope
  ``alaas.select.merge`` reaches the HLO metadata only: the trace names
  an op by its HLO text, which does not carry the scope, and the
  compiler may lower the all-gather to an all-reduce, so the op is known
  by its opcode;
* ``kernel_s``: the summed durations of its greedy-round kernel calls.

Everything is clipped to the span between the tracer's markers. A run
without a trace, markers or ``alaas.select.greedy`` annotations reads
None.
"""
from __future__ import annotations

from typing import List, Optional

from bench.harness import trace

LOOP = "alaas.select.greedy"
KERNEL = "_greedy_round"
COLLECTIVES = ("all-gather", "all-reduce", "collective-permute",
               "all-to-all", "reduce-scatter")


def opcode(op_name: str) -> str:
    """``%all-reduce.5 = s32[2056]{0} all-reduce(...)`` -> ``all-reduce``:
    the word after the result type (a tuple type is skipped whole)."""
    rest = op_name.split(" = ", 1)[-1]
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[-1]
    return rest.strip().split("(", 1)[0]


def is_collective(op_name: str) -> bool:
    kinds = (opcode(op_name), trace.op_kind(op_name))
    return any(k.startswith(COLLECTIVES) for k in kinds)


def _overlap(ivs, spans) -> float:
    """Seconds of the union of ``ivs`` inside the union of ``spans``."""
    total = 0
    for s, e in trace._union(ivs):
        for a, b in spans:
            lo, hi = max(s, a), min(e, b)
            if hi > lo:
                total += hi - lo
    return total * 1e-9


def chips(profile, n_chips: int) -> Optional[List[dict]]:
    """``[{"busy_s", "merge_s", "kernel_s"}]`` per chip, under the pick
    loop's annotations; None without markers or annotations."""
    marks = trace._markers(profile)
    if trace.MARK_START not in marks or trace.MARK_STOP not in marks:
        return None
    lo, hi = marks[trace.MARK_START], marks[trace.MARK_STOP]
    loops = []
    for p in profile.planes:
        if p.name.startswith(trace.DEVICE_PREFIX):
            continue
        for line in p.lines:
            for ev in line.events:
                if ev.name == LOOP:
                    s = int(ev.start_ns)
                    a, b = max(s, lo), min(s + int(ev.duration_ns), hi)
                    if b > a:
                        loops.append((a, b))
    loops = trace._union(loops)
    if not loops:
        return None
    planes = sorted(
        (p for p in profile.planes if p.name.startswith(trace.DEVICE_PREFIX)),
        key=lambda p: int(p.name[len(trace.DEVICE_PREFIX):]))[:n_chips]
    out = []
    for plane in planes:
        ops, merges, kernel = [], [], 0.0
        for line in plane.lines:
            if line.name != trace.OPS_LINE:
                continue
            for ev in line.events:
                s = int(ev.start_ns)
                iv = (s, s + int(ev.duration_ns))
                ops.append(iv)
                if is_collective(ev.name):
                    merges.append(iv)
                elif KERNEL in ev.name and "custom-call(" in ev.name:
                    kernel += _overlap([iv], loops)
        out.append({"busy_s": _overlap(ops, loops),
                    "merge_s": _overlap(merges, loops), "kernel_s": kernel})
    return out


def read(run) -> Optional[List[dict]]:
    """``chips`` of this run's trace (read once per run)."""
    if run.trace_reduction is None:
        return None
    if not hasattr(run, "lane_chips"):
        path = run.spec.root / "bench" / ".cache" / "trace" / run.spec.name
        try:
            run.lane_chips = chips(trace.load(str(path)), len(run.devices))
        except FileNotFoundError:
            run.lane_chips = None
    return run.lane_chips
