"""greedy_round_roofline: the least time the chip needs for the greedy
round kernel's calls in the window (harness.flops.greedy_round_work of
each call's pool rows N, width d and new centers R, at the bf16 peak and
the HBM bandwidth of bench/peaks.json), over the summed device time of
those calls, in percent. Each call's shapes come from its op's HLO text
in the trace; an operand the wrapper padded counts the rows of the pad's
input, the work the kernel was asked for."""
from bench.harness import flops, readers

KERNEL = "_greedy_round"


def read(run):
    red = run.trace_reduction
    if red is None:
        return None
    d = int(run.cfg["widths"][-1])
    pads = readers.padded_rows(red, d)
    calls = [(name, dur) for name, evs in red.op_events.items()
             if KERNEL in name and "custom-call(" in name
             for _, dur, _ in evs]
    if not calls:
        return None
    least = spent = 0.0
    for name, dur_ns in calls:
        shape = readers.round_shape(name, d, pads)
        if shape is None:
            return None
        n, r = shape
        f, b = flops.greedy_round_work(n, d, r)
        least += flops.least_time_s(f, b, readers.peak_flops(run),
                                    float(run.peaks["hbm_bytes_per_s"]))
        spent += dur_ns * 1e-9
    return 100.0 * least / spent if spent > 0 else None
