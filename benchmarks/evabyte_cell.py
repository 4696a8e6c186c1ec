"""Runner of the EvaByte serving cell: ``serve_cell``'s load generator,
window and comparison, driven as they are, with this configuration's weights
(``weights_evabyte``) and plain reference (``reference_evabyte``) in the
places of the GPT block's. ``serve_cell.run`` reaches both through its
module's names, so they are put there for the length of the call; nothing of
``serve_cell`` is copied or edited. What the served stack is stays
``tools/serve_lm.build_stack`` on a ``TransformerConfig`` built from the
configuration file.

After the run it adds, on the line before the result, what the window's
requests had left to prefill (``unmatched_prompt_tokens``: a cold document
inside the window shows as a five-figure maximum) and the EVA counters of
the window's rounds, from the program's span rings.
"""

from __future__ import annotations

from benchmarks import (
    program_spans,
    reference_evabyte,
    serve_cell,
    weights_evabyte,
)


def eva_rows(c: dict, lo=None, hi=None):
    """(summary rows, window rows, decode tokens, rounds) over the engine
    rounds of ``[lo, hi]`` (the window by default) that decoded; None where
    the program's rounds carry no such counts."""
    rs = [r for r in program_spans.rounds(c, lo, hi) or []
          if "summary_rows_read" in r]
    if not rs:
        return None
    return (sum(r["summary_rows_read"] for r in rs),
            sum(r["window_rows_read"] for r in rs),
            sum(r.get("active", 0) for r in rs), len(rs))


def run(ctx) -> dict:
    held = serve_cell.weights, serve_cell.reference
    serve_cell.weights, serve_cell.reference = (
        weights_evabyte, reference_evabyte)
    try:
        res = serve_cell.run(ctx)
    finally:
        serve_cell.weights, serve_cell.reference = held
    c = res["collected"]
    left = [p - m for m, p in c["counters"]["prompt_spans"]]
    extra = res["extra"]
    extra["unmatched_prompt_tokens"] = {
        "requests": len(left), "sum": sum(left), "max": max(left, default=0)}
    rows = eva_rows(c)
    if rows:
        extra["eva_window"] = {
            "summary_rows": rows[0], "window_rows": rows[1],
            "decode_tokens": rows[2],
            "rolls": len(program_spans.in_window(c, "engine.window_roll")
                         or [])}
    # Cold prefill: whole-width segments that do not end their prompt. The
    # lead is sized so that the last of them ends well before the window.
    chunks = program_spans.records(
        "engine.prefill_chunk", float("-inf"), c["t_close"]) or []
    width = int(ctx["serve_cfg"]["prefill_len"])
    cold = [r for r in chunks if r[2] and not r[2].get("final")
            and r[2].get("width") == width]
    before = [r[1] for r in cold if r[1] <= c["t_open"]]
    extra["cold_prefill"] = {
        "chunks_in_lead": len(before),
        "chunks_in_window": len(cold) - len(before),
        "last_ended_before_open_s": (
            c["t_open"] - max(before) if before else None)}
    print(f"evabyte_cell: cold prefill {extra['cold_prefill']}; "
          f"the window's requests had "
          f"{extra['unmatched_prompt_tokens']} prompt tokens left to "
          f"prefill; EVA rounds {extra.get('eva_window')}", flush=True)
    return res
