"""Per-request time per output token, 90th percentile over every window
request: (last token - first token) / (tokens - 1)."""
from harness import records


def read(run):
    return records.pct(run.window.tpot(), 90)
