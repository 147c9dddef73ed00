"""compile_s.round: seconds of XLA backend compiles (``compile.s``, each
charged to the program span open on the compiling thread) per round, over
the rounds that ended before the profiler started."""
from bench.harness import program_spans as ps


def read(run):
    return ps.per_untraced_round(
        run, lambda evs: ps.counted(evs, "compile.s"))
