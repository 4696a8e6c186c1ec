"""Prompt tokens adopted from the prefix cache over prompt tokens looked up: engine.stats, window delta."""


def read(c):
    n = c["counters"]
    if not n["prefix_tokens_total"]:
        return None
    return 100.0 * n["prefix_tokens_matched"] / n["prefix_tokens_total"]
