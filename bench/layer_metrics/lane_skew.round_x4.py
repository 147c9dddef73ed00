"""lane_skew.round_x4: the largest chip's summed greedy-round kernel time
under the pick loop (``alaas.select.greedy`` annotations) over the mean of
that time across the cell's chips; traced stretch
(``harness/lane_trace.py``). 1.0 is lanes in balance; a loop that runs
on one chip of n reads n."""
from bench.harness import lane_trace


def read(run):
    chips = lane_trace.read(run)
    times = [c["kernel_s"] for c in chips or []]
    if not times or not any(times):
        return None
    return max(times) / (sum(times) / len(times))
