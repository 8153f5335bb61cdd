"""Scheduled arrival to the admission hook's yes, 90th percentile."""
from harness import records


def read(run):
    w = run.window
    return records.pct([r.admitted - r.due for r in w.reqs], 90)
