"""Share of the window in which a join (admission to first token) was in
progress."""


def read(run):
    w = run.window
    return 100.0 * w.covered([(r.admitted, r.first) for r in w.reqs]) \
        / w.seconds
