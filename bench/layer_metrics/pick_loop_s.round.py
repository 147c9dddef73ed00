"""pick_loop_s.round: seconds per round in the program's k-center pick loop
(``alaas.select.greedy``), over the rounds that ended before the profiler
started."""
from bench.harness import program_spans as ps


def read(run):
    return ps.per_untraced_round(
        run, lambda evs: ps.span_s(evs, "alaas.select.greedy"))
