"""forward_idle_share.push: the first chip's idle share under the scorer's
forward (``alaas.embed.forward`` annotations) in the traced stretch."""
from bench.harness import program_spans as ps


def read(run):
    st = ps.stretch(run)
    return st.idle_share_under("alaas.embed.forward") if st else None
