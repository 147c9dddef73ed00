"""query_prep_s.round: seconds per round in the program's query
(``alaas.query``) outside its pick loop (``alaas.select.greedy``): the
ingest barrier, the artifact refresh, the gathers, uploads and the warm
k-center state; over the rounds that ended before the profiler started."""
from bench.harness import program_spans as ps


def read(run):
    return ps.per_untraced_round(
        run, lambda evs: ps.span_s(evs, "alaas.query")
        - ps.span_s(evs, "alaas.select.greedy"))
