"""idle_share.push: the device's idle share over the traced window of bulk pushes."""
from bench.harness import readers


def read(run):
    return readers.idle_share(run)
