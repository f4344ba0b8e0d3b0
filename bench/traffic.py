"""The one generator of the benchmark's load, driven by a traffic file of
``bench/traffic``.

Every seed gets the same work. Lengths are the quantiles of the stated
distribution in blocks of ``block`` requests (the window's requests as one
block of their own), each block a permutation of one fixed set; open-loop
gaps are the quantiles of the exponential, permuted and scaled so that each
segment of the run (pre-roll, window, each span after it) holds exactly
``rate x length`` arrivals. The orders come from the traffic file's
``schedule_seed``, so every run sends the same sizes at the same times;
the run's seed picks the prompts' tokens (and the weights). Tail latencies
depend on which sizes meet in the queue, so an order that changed with the
seed would change the work from run to run.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles of the distribution ``spec`` describes,
    rounded and clipped as it says."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = spec["min"] + u * (spec["max"] + 1 - spec["min"])
        v = np.floor(v)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    mult = spec.get("multiple", 1)
    v = np.ceil(v / mult) * mult
    return np.clip(v, spec["min"], spec["max"]).astype(np.int64)


def lengths(spec: dict, n: int, block: int, rng) -> np.ndarray:
    base = _quantiles(spec, block)
    out = [rng.permutation(base) for _ in range(-(-n // block))]
    return np.concatenate(out)[:n]


def arrival_offsets(rate: float, span_s: float, rng) -> np.ndarray:
    """Exactly round(rate * span_s) arrival offsets in [0, span_s): Poisson
    gaps as permuted quantiles of the exponential, scaled to the span."""
    n = max(1, int(round(rate * span_s)))
    u = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-u))
    t = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    # the first arrival of a segment falls after a gap too
    t = (t + gaps[-1] * rng.random()) * (span_s / gaps.sum())
    return np.minimum(t, span_s * (1 - 1e-9))


def prompts(vocab: int, plens, rng) -> list:
    return [rng.integers(0, vocab, int(n), dtype=np.int32) for n in plens]


def open_loop(t: dict, vocab: int, seconds: float, rng) -> dict:
    """Due times (seconds from the start of the loop), prompts and output
    budgets for the pre-roll, the window and ``drain_s`` after it; ``rng``
    (the run's) draws the tokens."""
    tok_rng, rng = rng, np.random.default_rng(t["schedule_seed"])
    segs = [(0.0, t["preroll_s"])]
    w0 = t["preroll_s"]
    segs.append((w0, seconds))
    end = w0 + seconds
    while end < w0 + seconds + t["drain_s"]:
        segs.append((end, seconds))
        end += seconds
    due, window = [], []
    for i, (start, span) in enumerate(segs):
        off = arrival_offsets(t["rate_per_s"], span, rng)
        due.append(start + off)
        window.append(np.full(off.shape, i == 1))
    due = np.concatenate(due)
    window = np.concatenate(window)
    n = len(due)
    blk = t["block"]
    # the window's requests draw their lengths as one block of their own,
    # so every seed sends the window the same multiset of sizes
    plen = np.empty(n, np.int64)
    olen = np.empty(n, np.int64)
    for mask, b in ((window, int(window.sum())), (~window, blk)):
        k = int(mask.sum())
        plen[mask] = lengths(t["prompt"], k, b, rng)
        olen[mask] = lengths(t["output"], k, b, rng)
    return {"due": due, "in_window": window, "prompts":
            prompts(vocab, plen, tok_rng), "max_new": olen,
            "window": (w0, w0 + seconds)}


def closed_loop(t: dict, vocab: int, n: int, rng) -> dict:
    """``n`` requests in the order a closed loop sends them; ``rng`` (the
    run's) draws the tokens."""
    blk = t["block"]
    order = np.random.default_rng(t["schedule_seed"])
    plen = lengths(t["prompt"], n, blk, order)
    olen = lengths(t["output"], n, blk, order)
    return {"prompts": prompts(vocab, plen, rng), "max_new": olen}


def distinct_prompt_lengths(spec: dict) -> list:
    """Every prompt length the distribution can give (the shapes to warm)."""
    mult = spec.get("multiple", 1)
    lo = math.ceil(spec["min"] / mult) * mult
    return list(range(lo, spec["max"] + 1, mult))
