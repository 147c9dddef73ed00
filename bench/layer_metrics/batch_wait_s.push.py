"""batch_wait_s.push: seconds the embed batcher waited to fill a batch
(``alaas.embed.batch_wait``) per 1,000 rows acknowledged before the
profiler started."""
from bench.harness import program_spans as ps


def read(run):
    return ps.per_1000_untraced_rows(run, "alaas.embed.batch_wait")
