"""The one traffic generator: draws every input of a run from its seed.

A traffic mix is a data file under ``bench/traffic/`` whose parameters this
module turns into arrays. Everything is drawn in bulk with numpy, in the
same amount for every seed, so that a seed changes the values and never the
work.

``powerlaw_pmf`` and ``draw_loads`` copy the load model of the SOAR paper's
Sec. 5 (a power law truncated to [1, 63], its exponent set so that the mean
is 5), as ``repro.core.tree.sample_load`` draws it one tenant at a time;
here a whole pool of tenants is drawn by one inverse-CDF lookup.
"""
from __future__ import annotations

import numpy as np


def rng(seed: int, stream: int = 0) -> np.random.Generator:
    """Generator for ``seed`` (any integer, however large) and a stream id,
    so that independent parts of a run draw independent numbers."""
    return np.random.default_rng([int(seed) % (1 << 64), int(stream)])


def powerlaw_pmf(lo: int, hi: int, mean: float) -> np.ndarray:
    """P(x) ~ x**-alpha on lo..hi with alpha set by bisection so that the
    mean is ``mean``."""
    x = np.arange(lo, hi + 1, dtype=np.float64)

    def pmf(alpha):
        p = x ** (-alpha)
        return p / p.sum()

    a_lo, a_hi = 0.0, 5.0                    # the mean falls as alpha grows
    for _ in range(80):
        mid = 0.5 * (a_lo + a_hi)
        if float((x * pmf(mid)).sum()) > mean:
            a_lo = mid
        else:
            a_hi = mid
    return pmf(0.5 * (a_lo + a_hi))


def draw_loads(g: np.random.Generator, shape: tuple, spec: dict
               ) -> np.ndarray:
    """Integer loads of the given shape, int16, from a load spec such as
    ``{"dist": "power-law", "lo": 1, "hi": 63, "mean": 5}``."""
    if spec["dist"] != "power-law":
        raise ValueError(f"unknown load distribution {spec['dist']!r}")
    lo, hi = int(spec["lo"]), int(spec["hi"])
    cdf = np.cumsum(powerlaw_pmf(lo, hi, float(spec["mean"])))
    u = g.random(shape)
    return (lo + np.searchsorted(cdf, u, side="right")).clip(
        lo, hi).astype(np.int16)


def split_counts(g: np.random.Generator, total: int, groups: int,
                 least: int, draws: int) -> np.ndarray:
    """``draws`` splits of ``total`` items over ``groups``, each group
    given at least ``least`` and the rest placed uniformly at random.
    Returns (draws, groups) int64 counts."""
    rest = total - least * groups
    if rest < 0:
        raise ValueError(f"{total} items cannot give {least} to each of "
                         f"{groups} groups")
    extra = g.multinomial(rest, np.full(groups, 1.0 / groups), size=draws)
    return extra + least
