"""Arithmetic shared by the per-layer metric readers.

Host-clock readings of a traced run (spans, rates) are taken over the
part of the window before the profiler started (``run.traced_from``), so
that the profiler's own cost does not enter them.
"""
from __future__ import annotations

import re

from bench.harness import flops


def idle_share(run):
    """1 - device busy / traced window, busy averaged over the chips."""
    red = run.trace_reduction
    if red is None or red.window_s <= 0 or red.busy_s <= 0:
        return None
    return 1.0 - red.busy_s / red.window_s


def untraced_rounds(run):
    """The window's rounds, (start, end, unlabeled rows, budget), that
    ended before the profiler started."""
    return [r for r in getattr(run, "round_log", [])
            if r[1] <= run.traced_from]


def per_round(run, name):
    """Seconds per round in the benchmark span ``name``, over the rounds
    that ended before the profiler started."""
    rounds = untraced_rounds(run)
    if not rounds:
        return None
    end = rounds[-1][1]
    total = sum(e - s for s, e in run.spans.get(name, []) if e <= end)
    return total / len(rounds)


def peak_flops(run):
    return float(run.peaks["bf16_flops_per_s"])


def embed_flops_per_row(run):
    cfg = run.cfg
    return flops.resnet_forward_flops(cfg["stage_sizes"], cfg["widths"],
                                      cfg["image_hw"], cfg["channels"])


_F32_2D = re.compile(r"f32\[(\d+),(\d+)\]")
_PAD = re.compile(r"^%pad[.\d]* = f32\[(\d+),(\d+)\].* pad\(f32\[(\d+),(\d+)\]")


def padded_rows(red, d: int) -> dict:
    """{padded rows: real rows} of the (rows, d) float32 pads in the
    trace: a kernel that pads its pool sees the padded count, and the
    work it was asked for is the real one."""
    out = {}
    for name in red.op_events:
        m = _PAD.match(name)
        if m and int(m.group(2)) == d == int(m.group(4)):
            rows, real = int(m.group(1)), int(m.group(3))
            out[rows] = min(out.get(rows, real), real)
    return out


def round_shape(op_name: str, d: int, pads: dict):
    """(N, R) of a greedy round call from its op's operand shapes: the
    (N, d) pool and the (R, d) centers are the largest and the smallest
    float32 matrices of width d; padded operands count their real rows."""
    args = op_name.split("custom-call(", 1)[-1]
    dims = sorted({int(a) for a, b in _F32_2D.findall(args) if int(b) == d})
    if len(dims) < 2:
        return None
    return pads.get(dims[-1], dims[-1]), pads.get(dims[0], dims[0])
