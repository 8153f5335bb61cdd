"""Whether what the timed path produced is correct.

Once the window has closed and the program's state is freed, a sample of
the finished window requests, drawn from the seed and always holding the
longest, is scored by the plain reference: one causal forward pass over
each prompt with its served tokens.  Each served token was the program's
greedy choice, so where the program is sound it is the reference's best
token, or one whose reference logit lies below the best by no more than
rounding.  The number compared is the widest such gap over every sampled
token; its limit is in the configuration file, with the readings it was
set from in PERF.md.  Every request must also have finished with its
token budget.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Verdict:
    correct: bool
    checks: dict          # short name -> {"value": v, "limit": l}


def sample(reqs, *, seed: int, min_tokens: int):
    """The longest finished request (prompt + output), then others in a
    seeded order until ``min_tokens`` served tokens are in the sample."""
    done = [r for r in reqs if r.n_tokens == r.max_new_tokens]
    if not done:
        return []
    done.sort(key=lambda r: r.rid)
    longest = max(done, key=lambda r: (r.prompt_len + r.n_tokens, -r.rid))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 11]) \
        .permutation(len(rest))
    out, n = [longest], longest.n_tokens
    for i in order:
        if n >= min_tokens:
            break
        out.append(rest[i])
        n += rest[i].n_tokens
    return out


def gaps(ref, weights, sizes, prompt, tokens, *, chooser=None):
    """Per served token: the reference's best logit minus its logit of the
    token.  With ``chooser`` set ("fp8" etc.), the token scored at each
    position is the one that reference precision puts first instead."""
    tokens = np.asarray(tokens, np.int32)
    fed = np.concatenate([np.asarray(prompt, np.int32), tokens[:-1]])
    first = len(prompt) - 1
    want = ref.logits(weights, fed, first, len(tokens), sizes)
    if chooser is not None:
        tokens = ref.logits(weights, fed, first, len(tokens), sizes,
                            precision=chooser).argmax(-1)
    return want.max(-1) - want[np.arange(len(tokens)), tokens]


def compare(ref, conf, sizes, key, reqs, *, seed: int, failed: int,
            chooser=None) -> Verdict:
    """The comparison a run reports.  ``chooser`` puts a lower-precision
    reference in the program's place (the control)."""
    import jax
    lim = conf["bench"]["correctness"]
    picked = sample(reqs, seed=seed, min_tokens=int(lim["sample_tokens"]))
    weights = jax.jit(lambda k: ref.init_weights(k, sizes))(key)
    widest, n_tok = 0.0, 0
    for r in picked:
        g = gaps(ref, weights, sizes, r.prompt, r.tokens, chooser=chooser)
        widest = max(widest, float(g.max()))
        n_tok += len(g)
    del weights
    checks = {
        "tokens_compared": {"value": n_tok,
                            "limit": int(lim["sample_tokens"]),
                            "rule": "at least"},
        "unfinished_requests": {"value": failed, "limit": 0,
                                "rule": "at most"},
        "max_logit_gap": {"value": widest,
                          "limit": float(lim["max_logit_gap"]),
                          "rule": "at most"},
    }
    ok = (bool(picked) and widest <= lim["max_logit_gap"]
          and n_tok >= int(lim["sample_tokens"]) and failed == 0)
    return Verdict(ok, checks)
