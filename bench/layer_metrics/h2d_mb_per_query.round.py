"""h2d_mb_per_query.round: megabytes the program uploads from the host to
the device (``h2d_bytes``) per query (``alaas.query``), in the window."""
from bench.harness import program_spans as ps


def read(run):
    evs = ps.events(run)
    queries = ps.span_count(evs, "alaas.query") if evs is not None else 0
    if not queries:
        return None
    return ps.counted(evs, "h2d_bytes") / 1e6 / queries
