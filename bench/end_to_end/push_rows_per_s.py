"""push_rows_per_s: rows acknowledged by synchronous pushes in the window,
over the time from the window's start to the last acknowledgement (pushes
open at the deadline finish and count)."""


def read(run):
    return run.e2e.get("push_rows_per_s")
