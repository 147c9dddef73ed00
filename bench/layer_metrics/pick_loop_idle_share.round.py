"""pick_loop_idle_share.round: the first chip's idle share under the
program's k-center pick loop (``alaas.select.greedy`` annotations) in the
traced stretch."""
from bench.harness import program_spans as ps


def read(run):
    st = ps.stretch(run)
    return st.idle_share_under("alaas.select.greedy") if st else None
