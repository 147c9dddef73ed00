"""retrain_s.round: seconds per round inside the client's train_eval (benchmark
span), over the rounds that ended before the profiler started."""
from bench.harness import readers


def read(run):
    return readers.per_round(run, "retrain")
