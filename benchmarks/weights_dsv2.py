"""DeepSeek-V2-Lite's weights from ``--seed``, made on the device in one
jitted call.

The tree has the names the program's ``TransformerLM`` expects of a latent
attention config (``block_i``: ``ln1``, ``q_proj`` (d, H (nope + rope)),
``kv_a`` (d, r + rope), ``kv_norm``, ``kv_b`` (r, H, nope + v), ``o_proj``
(H v, d), ``ln2``; then ``mlp_gate`` / ``mlp_up`` / ``mlp_out`` in the
``dense_layers`` leading layers and ``router`` / ``moe_in`` / ``moe_out`` /
``shared_in`` / ``shared_out`` in the rest; an untied ``lm_head``); the plain
reference (``reference_dsv2.py``) reads the same tree. The rope columns of
``q_proj`` and ``kv_a`` are in DeepSeek-V2's order (interleaved pairs), which
both read as published.

Kernels are normal with standard deviation 1/sqrt(fan_in), the norms'
weights 1 + normal x 0.05. **The router is drawn so that routing is near
even**, which is what this draw ASSUMES of the trained model (DeepSeek-V2
trains with balance losses, whose purpose is an even load): the share of
experts a round touches is this draw's, not the model's. As
``weights_nemotron`` does it, for the same two reasons (a part of the hidden
state that every token shares becomes one offset an expert through a linear
router; a network whose sublayers all write at full scale is chaotic, and a
flipped route flips more downstream):

* the embedding at unit scale and the sublayers' output matrices at
  ``RESIDUAL_GAIN`` of 1/sqrt(fan_in), so that through this stage's five
  layers a token's own embedding stays the larger part of its hidden state,
  as in the first layers of a trained model;
* the matrices that write into the residual stream (``o_proj``,
  ``mlp_out``, ``moe_out``, ``shared_out``) have the mean over their INPUT
  rows taken out of every output column, so that an activation's mean, the
  same for every token, writes nothing;
* the router's matrix at ``ROUTER_GAIN`` / sqrt(fan_in): the softmax scores
  of a token over 64 experts then spread over a few units of logit, so that
  its top six are its own and a bfloat16 residual seldom swaps the sixth.

None of this is a mechanism: the program and the reference compute the
published equations on whatever tree they are handed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.weights import seed_key
from benchmarks.weights_nemotron import _draw

EMBED_STD = 1.0
RESIDUAL_GAIN = 0.25
ROUTER_GAIN = 2.0


def leaf_shapes(cfg: dict) -> dict:
    """{path tuple: (shape, kind, std, mean)} for every parameter, kinds as
    ``weights_nemotron._draw`` takes them (``normal``, ``centred``)."""
    d, heads = int(cfg["d_model"]), int(cfg["num_heads"])
    r, nope, rope, vd = (int(cfg[k]) for k in (
        "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    vocab = int(cfg["vocab_size"])
    out = {("tok_embed", "embedding"): ((vocab, d), "normal", EMBED_STD, 0.0)}

    def dense(path, shape, fan_in, gain=1.0, kind="normal"):
        out[path] = (shape, kind, gain * fan_in ** -0.5, 0.0)

    def norm(path, width):
        out[path + ("scale",)] = ((width,), "normal", 0.05, 1.0)

    for i in range(int(cfg["num_layers"])):
        b = (f"block_{i}",)
        norm(b + ("ln1",), d)
        dense(b + ("q_proj",), (d, heads * (nope + rope)), d)
        dense(b + ("kv_a",), (d, r + rope), d)
        norm(b + ("kv_norm",), r)
        dense(b + ("kv_b",), (r, heads, nope + vd), r)
        dense(b + ("o_proj",), (heads * vd, d), heads * vd, RESIDUAL_GAIN,
              "centred")
        norm(b + ("ln2",), d)
        if i < int(cfg.get("dense_layers", 0)):
            ff = int(cfg["d_ff"])
            dense(b + ("mlp_gate", "kernel"), (d, ff), d)
            dense(b + ("mlp_up", "kernel"), (d, ff), d)
            dense(b + ("mlp_out", "kernel"), (ff, d), ff, RESIDUAL_GAIN,
                  "centred")
            continue
        n_exp = int(cfg["num_experts"])
        n_held = len(cfg.get("experts_held") or range(n_exp))
        width, wide = int(cfg["expert_width"]), int(cfg["shared_expert_width"])
        dense(b + ("router", "kernel"), (d, n_exp), d, ROUTER_GAIN)
        dense(b + ("moe_in",), (n_held, d, 2 * width), d)
        dense(b + ("moe_out",), (n_held, width, d), width, RESIDUAL_GAIN,
              "centred")
        dense(b + ("shared_in", "kernel"), (d, 2 * wide), d)
        dense(b + ("shared_out", "kernel"), (wide, d), wide, RESIDUAL_GAIN,
              "centred")
    norm(("ln_f",), d)
    dense(("lm_head", "kernel"), (d, vocab), d)
    return out


def make_params(cfg: dict, seed: int, dtype):
    """The whole tree in one jitted call, in ``dtype``."""
    shapes = leaf_shapes(cfg)

    def build(key):
        tree: dict = {}
        for n, (path, spec) in enumerate(shapes.items()):
            node = tree
            for part in path[:-1]:
                node = node.setdefault(part, {})
            node[path[-1]] = _draw(jax.random.fold_in(key, n), *spec, dtype)
        return tree

    return jax.jit(build)(seed_key(seed))
