"""syncs_per_pick.round: device-to-host scalar reads of the k-center pick
loop (``select.d2h_syncs``) per pick (``select.picks``), in the window."""
from bench.harness import program_spans as ps


def read(run):
    evs = ps.events(run)
    picks = ps.counted(evs, "select.picks") if evs is not None else 0
    if not picks:
        return None
    return ps.counted(evs, "select.d2h_syncs") / picks
