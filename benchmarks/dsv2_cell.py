"""Runner of the DeepSeek-V2-Lite serving cell: ``serve_cell``'s load
generator, window and comparison, driven as they are but for the closed
loop's laps after the first, which ask the pool's documents with fresh
tails (``fresh_tails``: in ``serve_cell`` a later lap repeats the first
word for word, so the prefix cache would adopt the whole prompt), with this
configuration's weights (``weights_dsv2``) and plain reference
(``reference_dsv2``, logits a block of rows at a time) in the places of the
GPT block's, and ``zaya_cell``'s token law (the traffic file's Zipf over the
whole vocabulary), as ``nemotron_cell`` does. What the served stack is stays
``tools/serve_lm.build_stack`` on a ``TransformerConfig`` built from the
configuration file.

The served path is prefill through the pool's latent rows (expanded or
absorbed by the chunk's width), prefix adoption of a cached document's
pages, and decode by the absorbed kernel over the pages; the reference is
the expanded form over the whole sequence, so every served position checks
the two forms against each other. Top-6 routing is not continuous: a
bfloat16 residual can swap the served model's sixth expert for the
reference's seventh where their scores nearly tie; every served position
is compared all the same (PERF.md §2 has the readings). ``--control`` takes
``int8``, ``fp8`` and ``reference_dsv2.FAULTS``.

After the run it adds, on the line before the result, the window's routing
and latent rows from the program's span rings, and the forms its prefills
took (``engine.start`` and ``engine.prefill_chunk``'s ``mla_form``).
"""

from __future__ import annotations

import collections
import types

import numpy as np

from benchmarks import (
    program_spans,
    reference_dsv2,
    serve_cell,
    traffic,
    weights_dsv2,
)
from benchmarks.zaya_cell import _zipf_traffic, moe_rounds


def fresh_tails(traffic_cfg: dict, seed: int, vocab: int, toy: bool):
    """``serve_cell.LoadGen`` whose closed loop asks each lap after the
    first with the same documents, tail lengths and outputs as the pool,
    and fresh tail tokens: a document assistant gets a new question each
    time. The tokens of lap ``l``'s request ``j`` are drawn from ``(seed,
    l, j)`` alone, through the file's token law, so the same seed gives the
    same requests whatever order the clients pull them in."""
    t = traffic.apply_toy(traffic_cfg, toy)
    sp = t["shared_prefix"]
    g = int(sp["groups"])
    doc_len = traffic.quantile_set(sp["prefix_len"], g)[traffic._mix(g, 29)]
    cdf = traffic.token_law(t, vocab)
    perm = traffic._mix(vocab, 7919)

    class FreshTails(serve_cell.LoadGen):
        def _client(self, pool, cursor):
            while not self.stop.is_set():
                with self.lock:
                    i = cursor[0]
                    cursor[0] += 1
                lap, j = divmod(i, len(pool))
                spec = pool[j]
                if lap:
                    n = int(doc_len[spec["group"]])
                    rng = np.random.default_rng([int(seed), 0x7A11, lap, j])
                    u = rng.random(len(spec["prompt"]) - n)
                    tail = perm[np.searchsorted(cdf, u).clip(0, vocab - 1)]
                    spec = dict(spec, prompt=spec["prompt"][:n]
                                + tuple(int(x) for x in tail))
                self._submit_and_follow(serve_cell.Req(spec))

    return FreshTails


def run(ctx) -> dict:
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.config import ServeConfig
    from distributed_tensorflow_tpu.models.transformer import (
        TransformerConfig,
    )

    # A program without this configuration's keys fails here, at once, and
    # not after 5.7 GB of weights have been made for it.
    TransformerConfig(**ctx["model_cfg"], compute_dtype=jnp.bfloat16)
    ServeConfig(**ctx["serve_cfg"])
    ref = reference_dsv2
    held = (serve_cell.weights, serve_cell.reference, serve_cell.traffic,
            serve_cell.LoadGen)
    serve_cell.weights = weights_dsv2
    serve_cell.reference = types.SimpleNamespace(
        logits=ref.Rows, gap_rows=ref.gap_rows, margin_rows=ref.margin_rows)
    serve_cell.traffic = _zipf_traffic()
    serve_cell.LoadGen = fresh_tails(
        ctx["traffic"], ctx["seed"], int(ctx["model_cfg"]["vocab_size"]),
        ctx["toy"])
    try:
        res = serve_cell.run(ctx)
    finally:
        (serve_cell.weights, serve_cell.reference, serve_cell.traffic,
         serve_cell.LoadGen) = held
    extra, c = res["extra"], res["collected"]
    # What set-up was made of (the build's spans, closed before the window).
    extra["setup_spans_s"] = {
        name: round(sum(r[1] - r[0] for r in program_spans.records(
            name, float("-inf"), c["t_open"]) or []), 2)
        for name in ("serve.build", "engine.place_weights", "engine.warmup")}
    rs = [r for r in moe_rounds(c) or [] if "latent_rows" in r]
    if rs:
        k = int(ctx["model_cfg"]["experts_per_token"])
        extra["routing"] = {
            "rounds": len(rs),
            "experts_touched_a_round": float(np.mean(
                [r["experts_touched"] for r in rs])),
            "of": rs[0].get("experts_total") or 0,
            "largest_expert_share_of_pairs": float(np.mean(
                [r["expert_tokens_max"] / max(1, k * r.get("active", 1))
                 for r in rs])),
            "latent_rows_a_round": float(np.mean(
                [r["latent_rows"] for r in rs])),
        }
    forms = collections.Counter()
    for name in ("engine.start", "engine.prefill_chunk"):
        for r in program_spans.in_window(c, name) or []:
            if r[2] and r[2].get("mla_form"):
                forms[(name, r[2]["mla_form"])] += 1
    extra["prefill_forms"] = {f"{n} {f}": v for (n, f), v in forms.items()}
    # Cold documents: whole-width chunks that do not end their prompt. The
    # first wave's do not all fit in the lead: the rest run in the window.
    width = int(ctx["serve_cfg"]["prefill_len"])
    cold = [r for r in program_spans.records(
        "engine.prefill_chunk", float("-inf"), c["t_close"]) or []
        if r[2] and not r[2].get("final") and r[2].get("width") == width]
    before = [r[1] for r in cold if r[1] <= c["t_open"]]
    extra["cold_prefill"] = {
        "chunks_in_lead": len(before),
        "chunks_in_window": len(cold) - len(before),
        "lead_s_a_chunk": ((max(before) - min(r[0] for r in cold))
                           / len(before)) if before else None,
        "last_ended_before_open_s": (
            c["t_open"] - max(before) if before else None)}
    print(f"dsv2_cell: routing and latent rows of the window's rounds "
          f"{extra.get('routing')}; prefill forms {extra['prefill_forms']}; "
          f"cold prefill {extra['cold_prefill']}", flush=True)
    return res
