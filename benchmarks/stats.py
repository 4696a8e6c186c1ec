"""Percentile and quartile arithmetic (copied from tools/loadgen.py's
nearest-rank-with-interpolation percentile; the original is listed in
PERF.md's open questions for deletion)."""

from __future__ import annotations

import statistics


def percentile(values, q: float):
    """q in [0, 100], linear interpolation between order statistics;
    None when there is nothing to read (never 0)."""
    vals = sorted(float(v) for v in values)
    if not vals:
        return None
    if len(vals) == 1:
        return vals[0]
    pos = (len(vals) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(vals) - 1)
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)


def spread(values) -> float:
    """Inter-quartile distance over the median, by the contract's rule
    (``statistics.quantiles(values, n=4)``)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def noise_scale(margins, gaps, lo: float = 1e-4, hi: float = 10.0,
                points: int = 400) -> float:
    """The scale of the noise on the logits that best explains which served
    tokens were not the reference's best.

    ``margins[i]`` is the reference's best logit minus its second best at
    position i, ``gaps[i]`` how far the served token's logit lies below the
    best (0 where it IS the best). Under noise of scale s on a logit
    difference, the runner-up overtakes the best with probability
    Phi(-margin / (s * sqrt 2)); the estimate is the s on a log grid that
    makes the observed pattern of overtakings most likely. Unlike the widest
    or the mean gap it does not depend on how many near-ties a seed's
    sequences happen to hold, and one token that is wrong by a wide margin
    (an altered token) drives it up by orders of magnitude."""
    import numpy as np
    from scipy.special import log_ndtr

    m = np.asarray(margins, np.float64)
    flipped = np.asarray(gaps, np.float64) > 0
    if not m.size:
        return lo
    grid = np.geomspace(lo, hi, points)
    z = -m[None, :] / (grid[:, None] * np.sqrt(2.0))
    ll = np.where(flipped[None, :], log_ndtr(z), log_ndtr(-z)).sum(1)
    return float(grid[int(np.argmax(ll))])
