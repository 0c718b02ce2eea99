"""The one traffic generator: reads a mix from ``traffic/<name>.json``.

Its ``loop`` is one of two:

* ``open``: requests fall due on a fixed schedule whatever the server
  does (independent users).  A run of ``seconds`` gets
  N = round(rate_per_s * seconds) requests: the N latent lengths are
  ``shares`` apportioned by largest remainder, the N gaps the
  mid-quantiles of an exponential (Poisson-like arrivals).
* ``backlog``: a fixed set of N = ``requests`` requests, lengths by
  ``shares`` as above, all due when the window opens; the window ends
  when the set is served (or after ``drain_s``), so latencies read the
  completion times over the set.  For requests so long that a window
  holds only a few of them.

Either order is drawn from ``schedule_seed`` when the mix names one (a
fixed trace that every run replays: a tail latency then reads the
server, not the luck of the draw) and from the run's seed otherwise.
A mix may also give ``trace_s``: a ``--trace 1`` run traces only the
window's first ``trace_s`` seconds (default: the whole window).
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


def schedule(traffic: dict, seed: int, seconds: float,
             rate: float | None = None) -> list[Arrival]:
    """The mix's arrivals, by its ``loop``."""
    if traffic["loop"] == "open":
        return open_schedule(traffic, seed, seconds, rate)
    if traffic["loop"] == "backlog":
        return backlog_schedule(traffic, seed)
    raise ValueError(f"loop {traffic['loop']!r}: not open or backlog")


def backlog_schedule(traffic: dict, seed: int) -> list[Arrival]:
    """The backlog's ``requests`` arrivals, all due at 0."""
    r = rng(traffic.get("schedule_seed", seed))
    return [Arrival(0.0, x) for x in lengths(
        traffic, int(traffic["requests"]), int(r.integers(2**63)))]


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
