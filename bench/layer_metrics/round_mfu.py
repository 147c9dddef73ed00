"""round_mfu: the work an AL round requires (harness.flops.al_round_flops:
the fold of the last round's new centers, one distance pass per pick, the
head's probabilities over the pool) at the chip's bf16 peak, over the
rounds' wall time times the chips; the rounds that ended before the
profiler started."""
from bench.harness import flops, readers


def read(run):
    rounds = readers.untraced_rounds(run)
    if not rounds:
        return None
    cfg = run.cfg
    total = sum(flops.al_round_flops(n_unl, cfg["pool_rows"],
                                     cfg["widths"][-1], cfg["num_classes"],
                                     budget) for _, _, n_unl, budget in rounds)
    wall = rounds[-1][1] - run.window[0]
    return 100.0 * total / (wall * len(run.devices)
                            * readers.peak_flops(run))
