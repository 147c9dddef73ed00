"""round_s: window start to the end of the last completed AL round, over
the rounds completed (the round open at the deadline finishes and
counts)."""


def read(run):
    return run.e2e.get("round_s")
