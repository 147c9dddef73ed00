"""merge_share.round_x4: per chip, the device time of the pick loop's
cross-chip merge (its collective ops, under the program's
``alaas.select.merge`` scope) over the chip's busy time under the pick
loop (``alaas.select.greedy`` annotations), mean over the cell's chips;
traced stretch (``harness/lane_trace.py``). Nothing to read where the loop
runs no collective."""
from bench.harness import lane_trace


def read(run):
    chips = [c for c in lane_trace.read(run) or [] if c["busy_s"] > 0]
    if not chips or not any(c["merge_s"] for c in chips):
        return None
    return sum(c["merge_s"] / c["busy_s"] for c in chips) / len(chips)
