"""Process start to window start: weights, engine, warm-up, compiles and
compile-cache reads."""


def read(run):
    return run.setup_s
