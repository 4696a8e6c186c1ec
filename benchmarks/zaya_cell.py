"""Runner of the ZAYA1 serving cell: ``serve_cell``'s load generator, window
and comparison, driven as they are, with this configuration's weights
(``weights_zaya``) and plain reference (``reference_zaya``) in the places of
the GPT block's, as ``evabyte_cell`` does. ``serve_cell.run`` reaches both,
and the traffic generator, through its module's names, so they are put there
for the length of the call; nothing of ``serve_cell`` is copied or edited.
What the served stack is stays ``tools/serve_lm.build_stack`` on a
``TransformerConfig`` built from the configuration file.

Two things are this cell's own.

* **Token ids by the traffic file's law.** The serving generator draws ids
  uniformly; the file's ``token_law`` (Zipf over the whole vocabulary) is
  applied to that draw through ``traffic.token_law``, the law the training
  batches use: id ``u`` of ``vocab`` becomes the law's quantile ``(u + 1/2)
  / vocab``, scattered over the vocabulary by the generator's permutation.
* **Logits a block at a time.** ``reference_zaya.Rows``: 3,584 positions x
  262,272 ids do not fit as one array beside the weights.

Top-1 routing is not continuous: a bfloat16 residual can send the served
model to another expert than the float32 reference's where the two best
scores nearly tie. Every served position is compared all the same; what
that costs is in the readings the cell's limits were set from (PERF.md §2).

After the run it adds, on the line before the result, the window's routing
from the program's span rings: experts touched a round (of layers x held)
and the largest expert's share of a round's tokens.
"""

from __future__ import annotations

import types

import numpy as np

from benchmarks import (
    program_spans,
    reference_zaya,
    serve_cell,
    traffic,
    weights_zaya,
)


def moe_rounds(c: dict, lo=None, hi=None):
    """The engine rounds of ``[lo, hi]`` (the window by default) that carry
    the routed layer's counts; None where the program's rounds have none."""
    rs = [r for r in program_spans.rounds(c, lo, hi) or []
          if "experts_touched" in r and r.get("active", 0) > 0]
    return rs or None


def _zipf_traffic():
    """``traffic`` with ``serve_plan``'s uniform ids mapped through the
    file's token law."""
    def serve_plan(cfg, seed, seconds, vocab, toy=False):
        plan = traffic.serve_plan(cfg, seed, seconds, vocab, toy=toy)
        if not cfg.get("token_law"):
            return plan
        cdf = traffic.token_law(cfg, vocab)
        perm = traffic._mix(vocab, 7919)
        for reqs in (plan.get(k) or [] for k in ("pool", "lead", "window")):
            for r in reqs:
                u = (np.asarray(r["prompt"], np.float64) + 0.5) / vocab
                ranks = np.searchsorted(cdf, u).clip(0, vocab - 1)
                r["prompt"] = tuple(int(x) for x in perm[ranks])
        return plan

    return types.SimpleNamespace(**dict(vars(traffic), serve_plan=serve_plan))


def _rows_reference():
    """``reference_zaya`` as the runner calls it, with logits as ``Rows``."""
    ref = reference_zaya
    return types.SimpleNamespace(
        logits=ref.Rows, gap_rows=ref.gap_rows, margin_rows=ref.margin_rows)


def run(ctx) -> dict:
    if int(ctx["config"]["num_experts_per_tok"]) != 1:
        raise ValueError(
            "models/moe.routed_experts is top-1; the configuration file "
            f"states num_experts_per_tok {ctx['config']['num_experts_per_tok']}")
    held = serve_cell.weights, serve_cell.reference, serve_cell.traffic
    serve_cell.weights = weights_zaya
    serve_cell.reference = _rows_reference()
    serve_cell.traffic = _zipf_traffic()
    try:
        res = serve_cell.run(ctx)
    finally:
        serve_cell.weights, serve_cell.reference, serve_cell.traffic = held
    extra, c = res["extra"], res["collected"]
    # What set-up was made of (the build's spans, closed before the window).
    extra["setup_spans_s"] = {
        name: round(sum(r[1] - r[0] for r in program_spans.records(
            name, float("-inf"), c["t_open"]) or []), 2)
        for name in ("serve.build", "engine.place_weights", "engine.warmup")}
    rs = moe_rounds(c)
    if rs:
        total = rs[0].get("experts_total") or 0
        extra["routing"] = {
            "rounds": len(rs),
            "experts_touched_a_round": float(np.mean(
                [r["experts_touched"] for r in rs])),
            "of": total,
            "largest_expert_share_of_tokens": float(np.mean(
                [r["expert_tokens_max"] / max(1, r.get("active", 1))
                 for r in rs])),
        }
    print(f"zaya_cell: routing of the window's rounds "
          f"{extra.get('routing')}", flush=True)
    return res
