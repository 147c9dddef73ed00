"""forward_s.push: seconds in the scorer's forward on the batcher thread
(``alaas.embed.forward``: dispatch, device and the copy back) per 1,000
rows acknowledged before the profiler started."""
from bench.harness import program_spans as ps


def read(run):
    return ps.per_1000_untraced_rows(run, "alaas.embed.forward")
