"""window_compiles.round: XLA compiles plus persistent-cache loads inside the window of rounds
(``jax.monitoring`` events, as ``chip_smoke.PhaseLog`` counts them)."""


def read(run):
    return run.window_compiles
