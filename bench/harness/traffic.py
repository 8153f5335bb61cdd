"""One general traffic generator, driven by a mix file (``bench/traffic/<name>.json``).

A mix file holds parameters only; adding a mix is adding a file.  Keys:

  rate          {"knee_req_per_s": K, "share_of_knee": s}: open-loop arrivals
                at K * s requests per second (the knee is measured once, by a
                sweep on the chip, and recorded in PERF.md).
  backlog       optional: this many further requests are due when the window
                opens (an offline batch's queue), ahead of the arrivals.
  segments      the prompt, as a list of pieces joined in order.  A piece is
                {"kind": "pooled", "pool": name, "count": n, "share": p,
                "length": dist}: with share p of the requests, one of n pooled
                token runs (a system prompt, a document), so prefixes repeat;
                with "under": pool, item k is always asked under item
                k mod count of that earlier pool (a document belongs to one
                application's system prompt), so a re-asked item is a cached
                prefix; or {"kind": "unique", "length": dist}: fresh tokens.
  output        dist of the number of tokens each request generates.
  max_context   prompt + output never exceeds this (output is cut to fit).
  warmup        {"requests": n, "max_new_tokens": m}: the set-up trace.  It
                draws from the window's own pool items, so the window starts
                with them cached, as a running server would.

A dist is {"dist": "fixed", "value": v}, {"dist": "uniform", "lo", "hi"} or
{"dist": "lognormal", "median", "sigma", "lo", "hi"}.

Every seed gets the same work: lengths and inter-arrival gaps are the
quantiles of their distributions at (i + 0.5) / n, dealt in one fixed order
(``LAYOUT``), so every seed serves the same requests at the same times with
the same pooled prefixes; the seed draws only the token ids.  In an open
loop the order is part of the work: which long prompts meet in one burst
sets the queue, so a seeded order moved a tail by half between seeds.  The
arrivals stay exponential-gapped (Poisson-like) and the lengths
heavy-tailed.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

# the fixed stream that deals lengths, gaps and pooled items to requests
LAYOUT = 20231017


@dataclasses.dataclass
class Planned:
    """One request of a trace: its scheduled arrival (seconds from the
    window's start), prompt token ids and output length."""
    rid: int
    arrival_s: float
    prompt: np.ndarray
    max_new_tokens: int
    pooled: dict            # pool name -> item index used (for bookkeeping)


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` values of ``dist`` at the probabilities (i + 0.5) / n, as ints."""
    p = (np.arange(n) + 0.5) / max(n, 1)
    kind = dist["dist"]
    if kind == "fixed":
        vals = np.full(n, float(dist["value"]))
    elif kind == "uniform":
        vals = dist["lo"] + p * (dist["hi"] - dist["lo"])
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in p])
        vals = dist["median"] * np.exp(dist["sigma"] * z)
    else:
        raise ValueError(f"unknown dist {kind!r}")
    lo, hi = dist.get("lo", -math.inf), dist.get("hi", math.inf)
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def rate_per_s(mix: dict) -> float:
    r = mix["rate"]
    return float(r["knee_req_per_s"]) * float(r["share_of_knee"])


def _balanced(rng, n: int, count: int) -> np.ndarray:
    """``n`` picks among ``count`` items, each used equally often (+-1),
    in a seeded order."""
    picks = np.arange(n) % count
    rng.shuffle(picks)
    return picks


def _pool_items(lay, tok, seg: dict, vocab: int) -> list[np.ndarray]:
    lens = quantiles(seg["length"], seg["count"])
    lay.shuffle(lens)
    return [tok.integers(0, vocab, int(L), dtype=np.int32) for L in lens]


def _build(lay, tok, mix: dict, n: int, vocab: int, arrivals: np.ndarray,
           outputs: np.ndarray, pools: dict) -> list[Planned]:
    """Requests from the layout stream ``lay`` (who gets which pooled
    item, unique lengths) and the token stream ``tok``."""
    pieces = [[] for _ in range(n)]
    used = [{} for _ in range(n)]
    for seg in mix["segments"]:
        if seg["kind"] == "pooled":
            items = pools[seg["pool"]]
            k = int(round(seg.get("share", 1.0) * n))
            who = lay.permutation(n)[:k]
            picks = _balanced(lay, k, len(items))
            for i, pick in sorted(zip(who, picks)):
                pieces[i].append(seg["pool"])
                used[i][seg["pool"]] = int(pick)
            owner = seg.get("under")
            for i in (who if owner else ()):
                if owner not in used[i]:
                    raise ValueError(f"pool {seg['pool']!r} is asked under "
                                     f"{owner!r}, which request {i} lacks")
                used[i][owner] = used[i][seg["pool"]] % len(pools[owner])
        elif seg["kind"] == "unique":
            lens = quantiles(seg["length"], n)
            lay.shuffle(lens)
            for i in range(n):
                pieces[i].append(tok.integers(0, vocab, int(lens[i]),
                                              dtype=np.int32))
        else:
            raise ValueError(f"unknown segment kind {seg['kind']!r}")
    out = []
    cap = int(mix["max_context"])
    for i in range(n):
        prompt = np.concatenate([
            pools[x][used[i][x]] if isinstance(x, str) else x
            for x in pieces[i]]).astype(np.int32)
        new = int(min(outputs[i], cap - len(prompt)))
        if new < 1:
            raise ValueError(f"mix leaves no room to generate: prompt "
                             f"{len(prompt)} of max_context {cap}")
        out.append(Planned(i, float(arrivals[i]), prompt, new, used[i]))
    return out


def make_trace(mix: dict, *, seed: int, seconds: float,
               vocab: int) -> tuple[list[Planned], list[Planned]]:
    """(window trace, warm-up trace) for one run.  The window holds the
    backlog and ``round(rate * seconds)`` arrivals, all in ``[0, seconds)``."""
    lay = np.random.default_rng(LAYOUT)
    tok = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32])
    rate = rate_per_s(mix)
    n_arr = max(int(round(rate * seconds)), 1)
    backlog = int(mix.get("backlog", 0))
    n = backlog + n_arr
    # exponential gaps by quantile, scaled so the last arrival falls half a
    # mean gap before the window closes
    p = (np.arange(n_arr) + 0.5) / n_arr
    gaps = -np.log1p(-p)
    lay.shuffle(gaps)
    arrivals = np.cumsum(gaps)
    arrivals *= (seconds - 0.5 / rate) / arrivals[-1]
    arrivals = np.concatenate([np.zeros(backlog), arrivals])
    outputs = quantiles(mix["output"], n)
    lay.shuffle(outputs)

    pooled = [s for s in mix["segments"] if s["kind"] == "pooled"]
    pools = {s["pool"]: _pool_items(lay, tok, s, vocab) for s in pooled}
    window = _build(lay, tok, mix, n, vocab, arrivals, outputs, pools)

    wu = mix["warmup"]
    nw = int(wu["requests"])
    wout = np.full(nw, int(wu["max_new_tokens"]))
    warm = _build(lay, tok, mix, nw, vocab, np.zeros(nw), wout, pools)
    return window, warm
