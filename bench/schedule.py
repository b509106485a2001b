"""The open-loop arrival schedule: one general generator, driven by the
parameters of a traffic file and the cell's rate.

Arrivals form a Poisson process of mean rate ``rate_qps`` whose intensity
may follow a repeating profile of phases (``phases``: ``[[share of the
period, rate multiplier], ...]`` over ``period_s``; one phase at 1.0 is a
steady Poisson stream).  Every seed gets the same work: the window holds
exactly ``round(rate · seconds)`` arrivals whose unit-rate gaps are the
exponential quantiles ``-ln(1 - (i + ½)/N)``, shuffled by the seed, then
mapped through the inverse cumulative intensity.  So seeds differ in the
order of the gaps and not in their number or their sizes.
"""

from __future__ import annotations

import numpy as np


def _profile(traffic: dict) -> tuple:
    """(period_s, [(start share, end share, multiplier)]) with the mean
    multiplier normalised to 1."""
    phases = traffic.get("phases", [[1.0, 1.0]])
    shares = np.array([p[0] for p in phases], np.float64)
    mults = np.array([p[1] for p in phases], np.float64)
    if (shares <= 0).any() or (mults < 0).any() or not mults.any():
        raise ValueError(f"bad phases {phases!r}")
    shares = shares / shares.sum()
    mults = mults / float((shares * mults).sum())
    edges = np.concatenate([[0.0], np.cumsum(shares)])
    return float(traffic.get("period_s", 1.0)), list(
        zip(edges[:-1], edges[1:], mults))


def _inverse_intensity(lam: np.ndarray, period: float, phases) -> np.ndarray:
    """Times t at which the cumulative intensity (in units of the mean
    rate times seconds) reaches ``lam``.  One period accumulates ``period``;
    a phase of multiplier ``mult`` accumulates ``mult`` per second."""
    whole = np.floor(lam / period)
    rest = lam - whole * period
    out = np.full(lam.shape, period)
    todo = np.ones(lam.shape, bool)
    acc = 0.0
    for lo, hi, mult in phases:
        if mult == 0:
            continue
        width = (hi - lo) * period * mult
        here = todo & (rest <= acc + width)
        out[here] = lo * period + (rest[here] - acc) / mult
        todo &= ~here
        acc += width
    return whole * period + out


def arrivals(seed: int, rate_qps: float, seconds: float,
             traffic: dict) -> np.ndarray:
    """Offsets in seconds from the window's start, ascending, all in
    ``[0, seconds)``."""
    n = int(round(rate_qps * seconds))
    if n < 1:
        raise ValueError(f"rate {rate_qps} over {seconds} s offers no request")
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)      # unit-rate quantiles
    rng = np.random.Generator(np.random.Philox(key=seed))
    gaps = gaps[rng.permutation(n)]
    lam = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    lam *= seconds / (lam[-1] + gaps[-1])            # N arrivals in the window
    period, phases = _profile(traffic)
    return np.minimum(_inverse_intensity(lam, period, phases),
                      np.nextafter(seconds, 0.0))


def pool_order(n_requests: int, pool_size: int) -> np.ndarray:
    """Pool query of each request: the pool cycled through in order."""
    return np.arange(n_requests) % pool_size
