"""Percentile and quartile arithmetic (copied from tools/loadgen.py's
nearest-rank-with-interpolation percentile; the original is listed in
PERF.md's open questions for deletion), and what a serving window counts
as its tokens. Nothing here imports JAX."""

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


def window_tokens(requests, t_open: float, t_close: float,
                  loop: str) -> dict:
    """What ``out_tok_s`` counts, by the loop of the cell.

    ``requests`` holds one ``(t_ref, asked, token_times)`` for every request
    of the run, the lead's included: when it was due (open loop) or sent
    (closed loop), its ``max_new_tokens``, and when each of its tokens
    reached the client. A request is the window's own when ``t_ref`` lies in
    ``[t_open, t_close)``.

    Closed loop: ``out_tokens`` is every token delivered inside the window,
    whoever sent it. The clients keep the slots full, so what crosses the
    opening is matched by what crosses the close and the count is the
    server's rate. Open loop: the tokens delivered inside the window of the
    window's own requests. The schedule sets what is asked for, so the count
    is at most the offered load and rises toward it as the server gets
    faster; counting the lead's leftovers as well (``delivered_tokens``,
    the count until PR 28) read the HIGHER the more backlog a slow server
    carried over the opening.

    Beside it: ``delivered_tokens`` (all the work the window delivered),
    ``carried_in_tokens`` (delivered inside the window by requests due or
    sent before it), ``cut_at_close_tokens`` (asked for by the window's own
    requests and not delivered by the close) and ``offered_tok_s`` (open
    loop: what the window's own requests ask for, over its seconds; None
    in a closed loop, where nothing is offered)."""
    if loop not in ("open", "closed"):
        raise ValueError(f"unknown loop {loop!r}")
    own = carried_in = delivered = asked_own = 0
    for t_ref, asked, times in requests:
        inside = sum(1 for t in times if t_open <= t < t_close)
        delivered += inside
        if t_ref is None:
            continue
        if t_ref < t_open:
            carried_in += inside
        elif t_ref < t_close:
            own += inside
            asked_own += int(asked)
    return {
        "out_tokens": delivered if loop == "closed" else own,
        "delivered_tokens": delivered,
        "carried_in_tokens": carried_in,
        "cut_at_close_tokens": asked_own - own,
        "offered_tok_s": (asked_own / (t_close - t_open)
                          if loop == "open" else None),
    }


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
