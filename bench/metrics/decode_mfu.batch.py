"""Decode's share of the chip's bf16 peak: output tokens per second in the
window times the model flops of one decoded token (projections, MLP and
head, plus attention at the window requests' mean context), over the
peak."""
from harness import flops


def read(run):
    w = run.window
    if run.peaks is None or not w.reqs:
        return None
    ctx = sum(r.prompt_len + r.n_tokens / 2 for r in w.reqs) / len(w.reqs)
    rate = w.tokens_in_window() / w.seconds
    return 100.0 * rate * flops.decode_token_flops(run.sizes, ctx) \
        / run.peaks["bf16_flops_per_s"]
