"""embed_mfu: rows acknowledged before the profiler started times the
ResNet forward's FLOPs per row (counted from shapes; padding rows do not
count), over that stretch of the window times the chips' bf16 peak, in
percent."""
from bench.harness import readers


def read(run):
    log = getattr(run, "push_log", None)
    end = min(run.traced_from, run.window[1])
    rows = sum(n for t, n in log or [] if t <= end)
    if not rows:
        return None
    return (100.0 * rows * readers.embed_flops_per_row(run)
            / ((end - run.window[0]) * len(run.devices)
               * readers.peak_flops(run)))
