"""Time to first token, 90th percentile over every window request:
scheduled arrival to the first token on the host (engine ``admit_t``)."""
from harness import records


def read(run):
    return records.pct(run.window.ttft(), 90)
