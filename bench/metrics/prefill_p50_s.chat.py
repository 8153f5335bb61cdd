"""Admission to first token on the host (the join: page copy, prefill or
chunked suffix prefill, first sample), median."""
from harness import records


def read(run):
    w = run.window
    return records.pct([r.first - r.admitted for r in w.reqs], 50)
