"""Output tokens on the host inside the window (first tokens at their join,
the rest at the end of the chunk that made them), over the window."""


def read(run):
    return run.window.tokens_in_window() / run.window.seconds
