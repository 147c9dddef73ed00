"""embed_pad_share: rows the scorer's forward computed in the window (the
batch size of each call) less the rows pushed, over the rows computed."""


def read(run):
    rows = getattr(run, "rows_pushed", None)
    fed = run.window_rows_fed
    if not rows or not fed:
        return None
    return (fed - rows) / fed
