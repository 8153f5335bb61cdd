"""Share of the traced part of the window with no operation on the device."""


def read(run):
    return run.trace.idle_share() if run.trace else None
