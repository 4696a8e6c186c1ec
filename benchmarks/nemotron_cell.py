"""Runner of the Nemotron-3-Nano serving cell: ``serve_cell``'s load
generator, window and comparison, driven as they are, with this
configuration's weights (``weights_nemotron``) and plain reference
(``reference_nemotron``) in the places of the GPT block's, as ``zaya_cell``
does, and with ``zaya_cell``'s token law (the traffic file's Zipf over the
whole vocabulary) and its reading of the rounds' expert counts. What the
served stack is stays ``tools/serve_lm.build_stack`` on a
``TransformerConfig`` built from the configuration file.

Top-6 routing is not continuous: a bfloat16 residual can swap the served
model's sixth expert for the reference's seventh where their scores nearly
tie, a sixth of the routed part. The reference routes by its own scores and
every served position is compared all the same; what that costs is in the
readings the cell's limits were set from (PERF.md §2). ``--control`` takes
``int8`` and ``reference_nemotron.FAULTS``.

The served tokens do not show the precision the slot state is held in (the
``state_bf16`` control passes, PERF.md §2), and the configuration states it:
float32 for the recurrent state, the cache's bfloat16 for the convolution's.
So the run also compares what the pool holds (``engine.stats``'
``ssm_state_bytes``, read once after the build) with the bytes of that
precision for every slot, at least: a state held in fewer bytes is another
result, and ``correct`` is false.

After the run it adds, on the line before the result, the window's routing
from the program's span rings (experts touched a round, of expert layers x
held; the largest expert's share of its layer's pairs) and the state bytes a
round moves (the live lanes' recurrent and convolution state, in and out).
"""

from __future__ import annotations

import types

import numpy as np

from benchmarks import (
    counts_nemotron,
    program_spans,
    reference_nemotron,
    serve_cell,
    weights_nemotron,
)
from benchmarks.zaya_cell import _zipf_traffic, moe_rounds


def state_bytes_a_round(cfg: dict, rs) -> float:
    """Mean bytes of slot state the rounds ``rs`` read and wrote: every live
    lane's, once in and once out."""
    return 2.0 * counts_nemotron.state_bytes_a_lane(cfg) * float(
        np.mean([r.get("ssm_lanes", 0) for r in rs]))


def run(ctx) -> dict:
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models.transformer import (
        TransformerConfig,
    )

    # A program without this configuration's keys fails here, at once, and
    # not after 12 GB of weights have been made for it.
    TransformerConfig(**ctx["model_cfg"], compute_dtype=jnp.bfloat16)
    ref = reference_nemotron
    held = serve_cell.weights, serve_cell.reference, serve_cell.traffic
    serve_cell.weights = weights_nemotron
    serve_cell.reference = types.SimpleNamespace(
        logits=ref.Rows, gap_rows=ref.gap_rows, margin_rows=ref.margin_rows)
    serve_cell.traffic = _zipf_traffic()
    build, pool_holds = serve_cell._build, []

    def building(ctx):
        stack = build(ctx)
        pool_holds.append(int(stack[1].stats["ssm_state_bytes"]))
        return stack

    serve_cell._build = building
    try:
        res = serve_cell.run(ctx)
    finally:
        serve_cell.weights, serve_cell.reference, serve_cell.traffic = held
        serve_cell._build = build
    res["compared"]["ssm_state_bytes"] = serve_cell._cmp(
        pool_holds[0], int(ctx["serve_cfg"]["slots"])
        * counts_nemotron.state_bytes_a_lane(ctx["model_cfg"]), at_least=True)
    extra, c = res["extra"], res["collected"]
    # What set-up was made of (the build's spans, closed before the window).
    extra["setup_spans_s"] = {
        name: round(sum(r[1] - r[0] for r in program_spans.records(
            name, float("-inf"), c["t_open"]) or []), 2)
        for name in ("serve.build", "engine.place_weights", "engine.warmup")}
    rs = moe_rounds(c)
    if rs:
        k = int(ctx["model_cfg"].get("experts_per_token", 1))
        extra["routing"] = {
            "rounds": len(rs),
            "experts_touched_a_round": float(np.mean(
                [r["experts_touched"] for r in rs])),
            "of": rs[0].get("experts_total") or 0,
            "largest_expert_share_of_pairs": float(np.mean(
                [r["expert_tokens_max"] / max(1, k * r.get("active", 1))
                 for r in rs])),
            "state_bytes_a_round": state_bytes_a_round(ctx["model_cfg"], rs),
        }
    print(f"nemotron_cell: routing and state of the window's rounds "
          f"{extra.get('routing')}", flush=True)
    return res
