"""loop_devices.round_x4: devices the k-center pick loop's program spanned
(``select.loop_devices``) per query (``alaas.query``), in the window.
Nothing to read for a program that does not count them."""
from bench.harness import program_spans as ps


def read(run):
    evs = ps.events(run)
    if evs is None or not any(e.name == "select.loop_devices" for e in evs):
        return None
    queries = ps.span_count(evs, "alaas.query")
    return ps.counted(evs, "select.loop_devices") / queries if queries \
        else None
