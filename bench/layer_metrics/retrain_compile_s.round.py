"""retrain_compile_s.round: seconds of XLA backend compiles (``compile.s``)
charged to the server's retrain (the innermost open span ``alaas.retrain``,
``alaas.retrain.fit`` or ``alaas.retrain.eval``) per round, over the rounds
that ended before the profiler started. Near zero where one compiled head
fit serves every labeled count of a bucket."""
from bench.harness import program_spans as ps

RETRAIN = {"alaas.retrain", "alaas.retrain.fit", "alaas.retrain.eval"}


def read(run):
    return ps.per_untraced_round(
        run, lambda evs: sum(e.value for e in evs if e.name == "compile.s"
                             and e.parent in RETRAIN))
