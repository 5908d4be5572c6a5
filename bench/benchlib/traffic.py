"""Open-loop request traffic from a mix's parameters (``bench/traffic/*.json``).

One mix is a rate and two truncated lognormal length distributions::

    {"rate": 2.0,                       # requests per second, Poisson
     "prompt": {"median": 1020, "sigma": 0.8, "min": 32, "max": 1792},
     "output": {"median": 129, "sigma": 0.8, "min": 8, "max": 256},
     "draw_seed": 20240501}

A length outside ``[min, max]`` is drawn again (never clipped), so the
lengths follow the lognormal conditioned on the range; the lognormal is
centred so that this conditioned distribution, the lengths offered, has
the stated ``median`` (``location``).

What a run's ``--seed`` changes and what it keeps: the arrival times and
the multiset of (prompt, output) length pairs come from ``draw_seed``, the
same for every run seed, so two runs offer the same work; the run seed
decides which arrival gets which pair, and the prompts' token ids. The
arrival times are one unit-rate Poisson stream scaled by ``1 / rate``, so a
sweep over rates offers the same stream, faster or slower.
"""
from __future__ import annotations

import math

import numpy as np


def location(spec: dict) -> float:
    """The log-space centre ``mu`` of the lognormal whose draws, rounded and
    kept inside ``[min, max]``, have the median ``spec["median"]``: the root
    of ``(Phi(z(m)) - Phi(z(a))) / (Phi(z(b)) - Phi(z(a))) = 1/2`` with
    ``z(x) = (ln x - mu) / sigma``, ``a = min - 1/2`` and ``b = max + 1/2``
    (the range a draw rounds into), by bisection: the left side falls as
    ``mu`` grows."""
    sigma = spec["sigma"]
    la, lb, lm = math.log(spec["min"] - 0.5), math.log(spec["max"] + 0.5), math.log(spec["median"])
    if not la < lm < lb:
        raise ValueError(f"median {spec['median']} outside [{spec['min']}, {spec['max']}]")
    phi = lambda x, mu: 0.5 * math.erfc((mu - x) / (sigma * math.sqrt(2.0)))  # noqa: E731

    def below(mu):
        a = phi(la, mu)
        return (phi(lm, mu) - a) / (phi(lb, mu) - a) - 0.5

    lo, hi = la, lb
    for _ in range(200):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if below(mid) > 0 else (lo, mid)
    return 0.5 * (lo + hi)


def lengths(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    """``n`` lengths from the truncated lognormal ``spec``, out-of-range
    draws redrawn."""
    mu, sigma = location(spec), spec["sigma"]
    out = np.empty(0, np.int64)
    while len(out) < n:
        x = np.rint(rng.lognormal(mu, sigma, size=2 * (n - len(out)) + 8)).astype(np.int64)
        out = np.concatenate([out, x[(x >= spec["min"]) & (x <= spec["max"])]])
    return out[:n]


def schedule(mix: dict, seconds: float):
    """The offer of one window: arrival times in ``[0, seconds)`` and the
    length pairs, in draw order (before a run seed pairs them up)."""
    rng = np.random.Generator(np.random.PCG64(mix["draw_seed"]))
    gaps = rng.exponential(1.0, size=int(4 * mix["rate"] * seconds) + 64)
    arrivals = np.cumsum(gaps) / mix["rate"]
    arrivals = arrivals[arrivals < seconds]
    n = len(arrivals)
    prompts = lengths(rng, mix["prompt"], n)
    outputs = lengths(rng, mix["output"], n)
    return arrivals, prompts, outputs


def requests(mix: dict, seed: int, seconds: float, vocab: int):
    """One window's requests for run ``seed``: ``(arrival_s, prompt tokens,
    output budget)`` triples in arrival order."""
    arrivals, prompts, outputs = schedule(mix, seconds)
    rng = np.random.Generator(np.random.PCG64(seed))
    order = rng.permutation(len(arrivals))
    out = []
    for t, i in zip(arrivals, order):
        toks = rng.integers(0, vocab, size=int(prompts[i]), dtype=np.int64).astype(np.int32)
        out.append((float(t), toks, int(outputs[i])))
    return out
