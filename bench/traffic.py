"""The one traffic generator: reads a mix from ``traffic/<name>.json``.

An open loop: requests fall due on a fixed schedule whatever the server
does (independent users).  A run of ``seconds`` gets
N = round(rate_per_s * seconds) requests: the N latent lengths are
``shares`` apportioned by largest remainder, the N gaps the
mid-quantiles of an exponential (Poisson-like arrivals), both in an
order drawn from ``schedule_seed`` when the mix names one (a fixed trace
that every run replays: a tail latency then reads the server, not the
luck of the draw) and from the run's seed otherwise.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    due: float  # seconds after the window opens
    length: int  # latent tokens


def apportion(shares: list[float], n: int) -> list[int]:
    """Split ``n`` by ``shares`` (largest remainder; sums to ``n``)."""
    total = float(sum(shares))
    raw = [s / total * n for s in shares]
    out = [math.floor(r) for r in raw]
    for i in sorted(range(len(raw)), key=lambda i: out[i] - raw[i])[
            : n - sum(out)]:
        out[i] += 1
    return out


def rng(seed: int) -> np.random.Generator:
    return np.random.Generator(np.random.PCG64(int(seed) & (2**64 - 1)))


def lengths(traffic: dict, n: int, seed: int) -> list[int]:
    counts = apportion(traffic["shares"], n)
    pool = [length for length, c in zip(traffic["lengths"], counts)
            for _ in range(c)]
    return [int(x) for x in rng(seed).permutation(pool)]


def open_schedule(traffic: dict, seed: int, seconds: float,
                  rate: float | None = None) -> list[Arrival]:
    """The open loop's arrivals; all fall due inside the window."""
    rate = traffic["rate_per_s"] if rate is None else rate
    n = max(1, round(rate * seconds))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    gaps *= seconds * (n - 0.5) / n / gaps.sum()
    r = rng(traffic.get("schedule_seed", seed))
    due = np.cumsum(r.permutation(gaps))
    lens = lengths(traffic, n, int(r.integers(2**63)))
    return [Arrival(float(t), x) for t, x in zip(due, lens)]
