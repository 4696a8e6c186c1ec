"""Weights from ``--seed``, made on the device in one jitted call.

The tree has the names the program's ``TransformerLM`` expects (it is an
input the benchmark hands to the program, like a checkpoint would be); the
benchmark's reference reads the same tree. Kernels are normal with standard
deviation 1/sqrt(fan_in), embeddings 1/sqrt(d_model); biases and LayerNorm
offsets are small and non-zero so that a dropped bias shows in the output.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A key from any whole number up to a little over 2**31 (and beyond):
    the low 31 bits seed it, the rest is folded in."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def leaf_shapes(cfg: dict) -> dict:
    """{path tuple: (shape, std, mean)} for every parameter."""
    d = int(cfg["d_model"])
    heads = int(cfg["num_heads"])
    kv = int(cfg.get("num_kv_heads") or heads)
    dh = d // heads
    ff = int(cfg["d_ff"])
    vocab = int(cfg["vocab_size"])
    bias = bool(cfg.get("use_bias", True))
    out = {("tok_embed", "embedding"): ((vocab, d), d ** -0.5, 0.0)}
    if cfg.get("position", "learned") == "learned":
        out[("pos_embed", "embedding")] = (
            (int(cfg["max_seq_len"]), d), 0.5 * d ** -0.5, 0.0)

    def dense(prefix, fan_in, fan_out):
        out[prefix + ("kernel",)] = ((fan_in, fan_out), fan_in ** -0.5, 0.0)
        if bias:
            out[prefix + ("bias",)] = ((fan_out,), 0.02, 0.0)

    def norm(prefix):
        out[prefix + ("scale",)] = ((d,), 0.05, 1.0)
        out[prefix + ("bias",)] = ((d,), 0.02, 0.0)

    for i in range(int(cfg["num_layers"])):
        b = (f"block_{i}",)
        norm(b + ("ln1",))
        dense(b + ("qkv",), d, d + 2 * kv * dh)
        dense(b + ("proj",), d, d)
        norm(b + ("ln2",))
        dense(b + ("mlp_in",), d, ff)
        dense(b + ("mlp_out",), ff, d)
    norm(("ln_f",))
    dense(("lm_head",), d, vocab)
    return out


def make_params(cfg: dict, seed: int, dtype):
    """The whole tree in one jitted call, in ``dtype``."""
    shapes = leaf_shapes(cfg)

    def build(key):
        tree: dict = {}
        for n, (path, (shape, std, mean)) in enumerate(shapes.items()):
            leaf = jax.random.normal(jax.random.fold_in(key, n), shape,
                                     jnp.float32) * std + mean
            node = tree
            for part in path[:-1]:
                node = node.setdefault(part, {})
            node[path[-1]] = leaf.astype(dtype)
        return tree

    return jax.jit(build)(seed_key(seed))
