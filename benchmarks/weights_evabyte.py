"""EvaByte's weights from ``--seed``, made on the device in one jitted call.

The tree has the names the program's ``TransformerLM`` expects of an EVA
config (``norm: rms``, ``mlp: swiglu``, ``eva_window`` set); the plain
reference (``reference_evabyte.py``) reads the same tree. Kernels are normal
with standard deviation 1/sqrt(fan_in), the embedding 1/sqrt(d_model); the
pooling vectors phi and mu are normal x head_dim^-1/2 and the norms' offsets
g normal x 0.05 (the weight is 1 + g), all non-zero so that a dropped term
shows in the logits.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.weights import seed_key


def leaf_shapes(cfg: dict) -> dict:
    """{path tuple: (shape, std)} for every parameter."""
    d = int(cfg["d_model"])
    heads = int(cfg["num_heads"])
    dh = d // heads
    ff = int(cfg["d_ff"])
    vocab = int(cfg["vocab_size"])
    out = {("tok_embed", "embedding"): ((vocab, d), d ** -0.5)}

    def dense(prefix, fan_in, fan_out):
        out[prefix + ("kernel",)] = ((fan_in, fan_out), fan_in ** -0.5)

    for i in range(int(cfg["num_layers"])):
        b = (f"block_{i}",)
        out[b + ("ln1", "scale")] = ((d,), 0.05)
        dense(b + ("qkv",), d, 3 * d)
        out[b + ("eva_phi",)] = ((heads, dh), dh ** -0.5)
        out[b + ("eva_mu",)] = ((heads, dh), dh ** -0.5)
        dense(b + ("proj",), d, d)
        out[b + ("ln2", "scale")] = ((d,), 0.05)
        dense(b + ("mlp_gate",), d, ff)
        dense(b + ("mlp_up",), d, ff)
        dense(b + ("mlp_out",), ff, d)
    out[("ln_f", "scale")] = ((d,), 0.05)
    dense(("lm_head",), d, vocab * int(cfg.get("num_pred_heads", 1)))
    return out


def make_params(cfg: dict, seed: int, dtype):
    """The whole tree in one jitted call, in ``dtype``."""
    shapes = leaf_shapes(cfg)

    def build(key):
        tree: dict = {}
        for n, (path, (shape, std)) in enumerate(shapes.items()):
            leaf = jax.random.normal(jax.random.fold_in(key, n), shape,
                                     jnp.float32) * std
            node = tree
            for part in path[:-1]:
                node = node.setdefault(part, {})
            node[path[-1]] = leaf.astype(dtype)
        return tree

    return jax.jit(build)(seed_key(seed))
