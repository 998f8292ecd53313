"""Traffic generator: one general generator driven by a traffic file.

The arrival processes and lognormal lengths follow the program's
``serving/workload.py`` (Poisson arrivals, lognormal lengths with the
median given and the range's ends as clamps, tiers by name), copied
here so the yardstick cannot move with the program. Two changes make a
run's work the same from seed to seed:

* every quantity is drawn by stratified quantiles, ``(i + 0.5) / n``,
  through the inverse distribution, so each seed gets the same multiset
  of inter-arrival gaps and lengths, separately in the lead-in, in the
  window and in the tail (the window always holds the same requests'
  sizes);
* the seed only permutes them: which request gets which length, and the
  order of the gaps.

A stream with ``"block": K`` draws its lengths per block of K
consecutive arrivals instead: every block holds the same K stratified
lengths, in seeded order. Above the knee the queue decides which
requests a window serves (those at its head, whatever their send
time), so only blocks give every seed the same work there.

A file with ``"schedule_seed": S`` draws every stream's arrivals and
lengths from ``S`` and not from the run's seed: every run offers the
same schedule, and the run's seed still draws the weights, the prompts'
token ids and the sample that ``correct`` checks. Above the knee a
window's work hangs on the order of the queue's head, which no per-seed
permutation keeps.

A traffic file (``traffic/<name>.json``) holds::

    {"lead_s": 10, "tail_s": 30, "drain_s": 60, "unfinished_ok": false,
     "serve": {"strategy": "hard", "live_policy": false},
     "streams": [
       {"tier": "standard", "arrival": "poisson", "rate": 2.0,
        "prompt": {"median": 512, "sigma": 0.8, "lo": 64, "hi": 3072},
        "output": {"median": 128, "sigma": 0.8, "lo": 16, "hi": 512}},
       {"tier": "priority", "arrival": "bursts", "size": 32,
        "spread_s": 1.0, "period_s": 10.0, "first_s": 5.0, ...}]}

Times are seconds relative to the start of the measured window; the
schedule starts ``lead_s`` before it and runs ``tail_s`` past its end.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List

import numpy as np


@dataclass(frozen=True)
class Planned:
    """One request as the load generator will send it."""
    rid: str
    t: float            # scheduled send, s from the window start
    tier: str
    prompt_len: int
    max_tokens: int


def rng_for(seed: int, salt: int) -> np.random.Generator:
    """A generator from a seed of any size (negative seeds fold in)."""
    return np.random.default_rng([int(seed) % (1 << 64), salt])


def lognormal_lengths(n: int, median: float, sigma: float, lo: int,
                      hi: int) -> np.ndarray:
    """``n`` stratified lognormal lengths, clamped to ``[lo, hi]``."""
    nd = NormalDist()
    u = (np.arange(n) + 0.5) / max(n, 1)
    z = np.array([nd.inv_cdf(float(x)) for x in u])
    v = np.rint(median * np.exp(sigma * z))
    return np.clip(v, lo, hi).astype(np.int64)


def exponential_gaps(n: int, rate: float) -> np.ndarray:
    """``n`` stratified exponential inter-arrival gaps at ``rate``/s."""
    u = (np.arange(n) + 0.5) / max(n, 1)
    return -np.log1p(-u) / rate


def _lengths(spec: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    v = lognormal_lengths(n, spec["median"], spec["sigma"], spec["lo"],
                          spec["hi"])
    return rng.permutation(v)


def _poisson(rate: float, start: float, end: float,
             rng: np.random.Generator) -> np.ndarray:
    """round(rate x span) arrivals in [start, end): stratified gaps in
    seeded order, scaled so the last arrival falls one mean gap short
    of ``end``."""
    n = int(round(rate * (end - start)))
    if n == 0:
        return np.zeros((0,))
    gaps = rng.permutation(exponential_gaps(n, rate))
    t = np.cumsum(gaps)
    return start + t * ((end - start) * n / (n + 1)) / t[-1]


def _stream_times(s: Dict, start: float, end: float, window_s: float,
                  rng: np.random.Generator) -> List[np.ndarray]:
    """Arrival times per segment: the lead-in before the window, the
    window, and the tail after it. Each segment is stratified on its
    own, so every seed puts the same number of requests, with the same
    lengths, into the window."""
    kind = s["arrival"]
    segs = [(start, 0.0), (0.0, window_s), (window_s, end)]
    if kind == "poisson":
        return [_poisson(s["rate"], a, b, rng) for a, b in segs if b > a]
    if kind == "bursts":
        t0 = s.get("first_s", 0.0)
        starts = []
        while t0 < s.get("until_s", end):
            if t0 >= start:
                starts.append(t0)
            t0 += s["period_s"]
        size, spread = int(s["size"]), float(s["spread_s"])
        offs = (np.arange(size) + 0.5) / size * spread
        return [b + rng.permutation(offs) for b in starts]
    raise ValueError(f"unknown arrival process {kind!r}")


def _blocked(spec: Dict, n: int, k: int,
             rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths, every block of ``k`` the same ``k`` stratified
    lengths in seeded order."""
    base = lognormal_lengths(k, spec["median"], spec["sigma"], spec["lo"],
                             spec["hi"])
    out = [rng.permutation(base) for _ in range(-(-n // k))]
    return np.concatenate(out)[:n] if out else base[:0]


def plan(traffic: Dict, window_s: float, seed: int,
         salt: int = 0) -> List[Planned]:
    """The whole schedule of one run, sorted by send time. ``salt``
    draws another schedule from the same seed (the warm-up's)."""
    start = -float(traffic.get("lead_s", 0.0))
    end = float(window_s) + float(traffic.get("tail_s", 0.0))
    seed = int(traffic.get("schedule_seed", seed))
    out: List[Planned] = []
    for si, s in enumerate(traffic["streams"]):
        rng = rng_for(seed, si + 1000 * salt)
        tag = s.get("tier", "standard")[0]
        segs = [t[t < end] for t in
                _stream_times(s, start, end, window_s, rng)]
        if s.get("block"):
            t = np.sort(np.concatenate(segs)) if segs else np.zeros(0)
            kb = int(s["block"])
            lens = [(t, _blocked(s["prompt"], len(t), kb, rng),
                     _blocked(s["output"], len(t), kb, rng))]
        else:
            lens = [(t, _lengths(s["prompt"], len(t), rng),
                     _lengths(s["output"], len(t), rng)) for t in segs]
        k = 0
        for times, prompts, outputs in lens:
            for i in range(len(times)):
                out.append(Planned(rid=f"{tag}{si}.{k}", t=float(times[i]),
                                   tier=s.get("tier", "standard"),
                                   prompt_len=int(prompts[i]),
                                   max_tokens=int(outputs[i])))
                k += 1
    out.sort(key=lambda p: p.t)
    return out
