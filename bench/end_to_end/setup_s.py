"""setup_s: process start to the window's start (JAX start-up, inputs and
weights, the server, the pool, warm-up and, on a cold cache, compiles)."""


def read(run):
    return run.e2e.get("setup_s")
