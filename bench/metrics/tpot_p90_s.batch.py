"""Per-request time per output token, 90th percentile over every window
request: (last token - first token) / (tokens - 1).  Above the knee a tail
swings with the queue, so in the batch cell it is a per-layer reading."""
from harness import records


def read(run):
    return records.pct(run.window.tpot(), 90)
