"""select_s.round: seconds per round inside the client's k-center query
(benchmark span), over the rounds that ended before the profiler started."""
from bench.harness import readers


def read(run):
    return readers.per_round(run, "select")
