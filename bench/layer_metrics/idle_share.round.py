"""idle_share.round: the device's idle share over the traced window of AL rounds."""
from bench.harness import readers


def read(run):
    return readers.idle_share(run)
