"""The one general traffic generator. A traffic mix is a data file under
``benchmarks/traffic/``; this module turns it and ``--seed`` into requests
(serving) or batches (training).

Every seed gets the SAME sizes and arrivals: lengths and inter-arrival gaps
are the stratified quantiles of their distribution (no draw), put in an order
that the traffic file's ``order_seed`` fixes. ``--seed`` gives the token ids
(and, in the runner, the weights). So two seeds differ in content, not in
the amount of work nor in when it arrives: the order of a few bursts decides
a 95th percentile over a hundred requests, and a tail that moves with the
seed's order could not be held to a bound of a few percent.
"""

from __future__ import annotations

import math

import numpy as np


def quantile_set(spec: dict, n: int) -> np.ndarray:
    """n whole numbers at the stratified quantiles of ``spec``'s law."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = float(spec["min"]), float(spec["max"])
    if spec["dist"] == "loguniform":
        v = np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    elif spec["dist"] == "uniform":
        v = lo + u * (hi - lo)
    elif spec["dist"] == "fixed":
        v = np.full(n, lo)
    else:
        raise ValueError(f"unknown length law {spec['dist']!r}")
    return np.maximum(1, np.round(v)).astype(np.int64)


def exp_gaps(n: int, rate: float) -> np.ndarray:
    """n inter-arrival gaps at the stratified quantiles of Exp(rate),
    rescaled so that they sum to exactly n / rate."""
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u)
    return g * (n / rate) / g.sum()


def zipf_counts(groups: int, s: float, n: int) -> np.ndarray:
    """How many of n requests each rank gets under Zipf(s): largest
    remainder, so the multiset is the same for every seed."""
    w = 1.0 / np.arange(1, groups + 1) ** s
    exact = w / w.sum() * n
    counts = np.floor(exact).astype(np.int64)
    order = np.argsort(-(exact - counts), kind="stable")
    counts[order[: n - counts.sum()]] += 1
    return counts


def _mix(n: int, stride: int = 37) -> np.ndarray:
    """A fixed permutation of range(n) that scatters neighbours."""
    while math.gcd(stride, n) != 1:
        stride += 1
    return (np.arange(n) * stride) % n


def apply_toy(traffic: dict, toy: bool) -> dict:
    t = dict(traffic)
    if toy:
        t.update(traffic.get("toy", {}))
    return t


def _phase(t: dict, n: int, rng, order_rng, vocab: int, prefixes):
    """n requests' (prompt, output length) under mix ``t``: the order from
    ``order_rng``, the token ids from ``rng``."""
    out_len = quantile_set(t["output_len"], n)[_mix(n, 41)]
    sp = t.get("shared_prefix")
    if sp:
        counts = zipf_counts(int(sp["groups"]), float(sp["zipf_s"]), n)
        group = np.repeat(np.arange(len(counts)), counts)
        tail_len = quantile_set(sp["tail_len"], n)[_mix(n)]
    else:
        prompt_len = quantile_set(t["prompt_len"], n)[_mix(n)]
    order = order_rng.permutation(n)
    reqs = []
    for i in order:
        if sp:
            tail = rng.integers(0, vocab, int(tail_len[i]), dtype=np.int32)
            prompt = np.concatenate([prefixes[group[i]], tail])
            g = int(group[i])
        else:
            prompt = rng.integers(0, vocab, int(prompt_len[i]),
                                  dtype=np.int32)
            g = -1
        reqs.append({"prompt": tuple(int(x) for x in prompt),
                     "max_new_tokens": int(out_len[i]), "group": g})
    return reqs


def serve_plan(traffic: dict, seed: int, seconds: float, vocab: int,
               toy: bool = False) -> dict:
    """Requests for one run. Open loop: ``lead`` and ``window`` lists of
    requests with ``due_s`` counted from the start of the generator (the
    window opens at ``lead_s``). Closed loop: one ``pool`` the clients pull
    from in order; the first round's outputs are cut to (i+1)/clients of
    their length so that slots are at mixed phases when the window opens."""
    t = apply_toy(traffic, toy)
    rng = np.random.default_rng([int(seed), 0x5EED])
    order_rng = np.random.default_rng([int(t["order_seed"]), 0x0DE4])
    sp = t.get("shared_prefix")
    prefixes = None
    if sp:
        g = int(sp["groups"])
        plen = quantile_set(sp["prefix_len"], g)[_mix(g, 29)]
        prefixes = [rng.integers(0, vocab, int(n), dtype=np.int32)
                    for n in plen]
    lead_s = float(t.get("lead_s", 0.0))
    if t["loop"] == "closed":
        n = int(t["pool_requests"])
        pool = _phase(t, n, rng, order_rng, vocab, prefixes)
        c = int(t["clients"])
        for i in range(min(c, n)):
            pool[i]["max_new_tokens"] = max(
                1, pool[i]["max_new_tokens"] * (i + 1) // c)
        return {"loop": "closed", "clients": c, "pool": pool,
                "lead_s": lead_s}
    rate = float(t["rate_per_s"])
    plan = {"loop": "open", "lead_s": lead_s, "rate_per_s": rate}
    t0 = 0.0
    for name, span in (("lead", lead_s), ("window", float(seconds))):
        n = max(1, int(round(rate * span)))
        reqs = _phase(t, n, rng, order_rng, vocab, prefixes)
        c = np.cumsum(exp_gaps(n, rate)[order_rng.permutation(n)])
        due = t0 + c / c[-1] * span * (1.0 - 0.5 / n)  # all inside the span
        for r, d in zip(reqs, due):
            r["due_s"] = float(d)
        plan[name] = reqs
        t0 += span
    return plan


def token_law(traffic: dict, vocab: int) -> np.ndarray:
    law = traffic["token_law"]
    if law["dist"] != "zipf":
        raise ValueError(f"unknown token law {law['dist']!r}")
    w = 1.0 / np.arange(1, vocab + 1) ** float(law["s"])
    return np.cumsum(w / w.sum())


class BatchStream:
    """Training batches (global_batch, seq) of token ids, drawn from the
    seed by the traffic file's law, made on the host one at a time. Rank r
    of the law is token id ``perm[r]``: a fixed scatter over the
    vocabulary, the same for every seed."""

    def __init__(self, traffic: dict, seed: int, batch: int, seq: int,
                 vocab: int):
        self.rng = np.random.default_rng([int(seed), 0xBA7C])
        self.cdf = token_law(traffic, vocab)
        self.perm = _mix(vocab, 7919).astype(np.int32)
        self.shape = (int(batch), int(seq))

    def next(self) -> np.ndarray:
        u = self.rng.random(self.shape)
        ranks = np.searchsorted(self.cdf, u).clip(0, len(self.perm) - 1)
        return self.perm[ranks]
