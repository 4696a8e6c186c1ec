"""Nemotron-3-Nano's weights from ``--seed``, made on the device in one
jitted call.

The tree has the names the program's ``TransformerLM`` expects of a
``layer_pattern`` config (``block_i`` holds one norm ``ln1`` and one
sublayer's parameters: ``in_proj`` / ``conv_w`` / ``conv_b`` / ``A_log`` /
``D`` / ``dt_bias`` / ``ssm_norm`` / ``out_proj`` for ``M``; ``router`` /
``router_bias`` / ``moe_up`` / ``moe_out`` / ``shared_in`` / ``shared_out``
for ``E``; ``qkv`` / ``proj`` for ``*``; an untied ``lm_head``); the plain
reference (``reference_nemotron.py``) reads the same tree.

Kernels are normal with standard deviation 1/sqrt(fan_in), the embedding
``EMBED_STD`` (unit scale), the norms' weights 1 + normal x 0.05, the
convolution normal x taps^-1/2 with a bias of normal x 0.1. The Mamba scalars
follow the family's initialisation, so that the decays are those of a real
model: ``A`` uniform in [1, 16] (``A_log`` its logarithm), ``dt_bias`` the
inverse softplus of a log-uniform draw in [``time_step_min``,
``time_step_max``] = [0.001, 0.1] floored at ``time_step_floor`` 1e-4, ``D``
ones.

**The router is drawn so that routing is near even and steady under
rounding**, which is what this draw ASSUMES of the trained model (its bias is
that of auxiliary-loss-free balancing, whose purpose is an even load): the
share of experts a round touches is this draw's, not the model's. Two things
make a random network route badly. A part of the hidden state that every
token shares becomes, through a linear router, one offset an expert, the same
for every token (PR 35: 72% of a round's tokens on one of ZAYA1's experts).
And with every sublayer at full scale the network is chaotic: a flipped route
changes a sixth of the routed part, which flips more routes downstream (20%
of tokens off the reference's best under bfloat16 linear layers, 60% under
int8, at a middle size on the CPU). What this draw does (what was tried, with
its readings, is in PERF.md section 6 and CHANGES.md under PR 37):

* the embedding at unit scale and the sublayers' output matrices at
  ``RESIDUAL_GAIN`` of 1/sqrt(fan_in) (the Mamba layers' 1.0, so that the
  state is a large part of the stream; the expert and attention layers'
  0.25), so that through this stage's nine layers a token's own embedding
  stays the larger part of its hidden state, as in the first layers of a
  trained model;
* the matrices that write into the residual stream behind an activation
  with a positive mean (``moe_out``, ``shared_out``: relu^2; ``out_proj``:
  the gated norm of a SiLU'd convolution) have the mean over their INPUT
  rows taken out of every output column, so that the mean activation, the
  same for every token, writes nothing;
* the router's matrix is ``ROUTER_GAIN`` (1.0) / sqrt(fan_in) and the
  balancing bias normal x ``ROUTER_BIAS_STD`` (0.01), drawn and not trained:
  the bias acts in SIGMOID space, where the chosen scores of a router at
  twice this gain sit 0.014 apart, so that a bias of 0.02 was most of the
  choice (100 of 128 experts touched where 122 is even), and at four times
  the gain the sigmoid saturates to 1.0 in float32 and the top-k breaks ties
  by index (60 of 128).

None of this is a mechanism: the program and the reference compute the
published equations on whatever tree they are handed.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.weights import seed_key

EMBED_STD = 1.0
RESIDUAL_GAIN = {"M": 1.0, "E": 0.25, "*": 0.25}
ROUTER_GAIN = 1.0
ROUTER_BIAS_STD = 0.01


def held_experts(cfg: dict) -> tuple:
    held = cfg.get("experts_held")
    return tuple(range(int(cfg["num_experts"]))) if held is None else tuple(
        int(e) for e in held)


def gated(cfg: dict) -> bool:
    return cfg.get("expert_act", "swiglu") == "swiglu"


def leaf_shapes(cfg: dict) -> dict:
    """{path tuple: (shape, kind, a, b)} for every parameter: ``normal``
    (std a, mean b), ``centred`` (normal, the mean over axis -2 taken out),
    ``a_log`` (log of uniform [a, b]), ``dt_bias`` (inverse softplus of
    log-uniform [a, b])."""
    d = int(cfg["d_model"])
    heads, kv = int(cfg["num_heads"]), int(cfg["num_kv_heads"])
    dh = int(cfg["head_dim"])
    out = {("tok_embed", "embedding"): (
        (int(cfg["vocab_size"]), d), "normal", EMBED_STD, 0.0)}

    def dense(prefix, fan_in, fan_out, gain=1.0, kind="normal"):
        out[prefix + ("kernel",)] = (
            (fan_in, fan_out), kind, gain * fan_in ** -0.5, 0.0)

    for i, kind in enumerate(cfg["layer_pattern"]):
        b = (f"block_{i}",)
        out[b + ("ln1", "scale")] = ((d,), "normal", 0.05, 1.0)
        if kind == "M":
            h, p = int(cfg["ssm_heads"]), int(cfg["ssm_head_dim"])
            taps = int(cfg["ssm_conv"])
            inner = h * p
            cw = inner + 2 * int(cfg["ssm_groups"]) * int(cfg["ssm_state"])
            dense(b + ("in_proj",), d, inner + cw + h)
            out[b + ("conv_w",)] = ((cw, taps), "normal", taps ** -0.5, 0.0)
            out[b + ("conv_b",)] = ((cw,), "normal", 0.1, 0.0)
            out[b + ("A_log",)] = ((h,), "a_log", 1.0, 16.0)
            out[b + ("dt_bias",)] = ((h,), "dt_bias", 0.001, 0.1)
            out[b + ("D",)] = ((h,), "normal", 0.0, 1.0)
            out[b + ("ssm_norm",)] = ((inner,), "normal", 0.05, 1.0)
            dense(b + ("out_proj",), inner, d, RESIDUAL_GAIN["M"], "centred")
        elif kind == "E":
            n_exp, n_held = int(cfg["num_experts"]), len(held_experts(cfg))
            width, wide = int(cfg["expert_width"]), int(
                cfg.get("shared_expert_width", 0))
            fan = 2 if gated(cfg) else 1
            dense(b + ("router",), d, n_exp, ROUTER_GAIN)
            out[b + ("router_bias",)] = ((n_exp,), "normal", ROUTER_BIAS_STD, 0.0)
            if gated(cfg):
                out[b + ("moe_in",)] = (
                    (n_held, d, 2 * width), "normal", d ** -0.5, 0.0)
            else:  # (out, in), as a checkpoint holds a linear layer
                out[b + ("moe_up",)] = (
                    (n_held, width, d), "normal", d ** -0.5, 0.0)
            out[b + ("moe_out",)] = (
                (n_held, width, d), "centred",
                RESIDUAL_GAIN["E"] * width ** -0.5, 0.0)
            if wide:
                dense(b + ("shared_in",), d, fan * wide)
                dense(b + ("shared_out",), wide, d, RESIDUAL_GAIN["E"],
                      "centred")
        else:
            dense(b + ("qkv",), d, (heads + 2 * kv) * dh)
            dense(b + ("proj",), heads * dh, d, RESIDUAL_GAIN["*"])
    out[("ln_f", "scale")] = ((d,), "normal", 0.05, 1.0)
    dense(("lm_head",), d, int(cfg["vocab_size"]))
    return out


def _draw(key, shape, kind, a, b, dtype):
    if len(shape) == 3:
        # One expert at a time: a layer's experts are 1.3 GB in bfloat16,
        # and their float32 draw beside 12 GB of weights would not fit.
        return jax.lax.map(
            lambda k: _draw(k, shape[1:], kind, a, b, dtype),
            jax.random.split(key, shape[0]))
    if kind == "a_log":
        return jnp.log(
            jax.random.uniform(key, shape, jnp.float32, a, b)).astype(dtype)
    if kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(a), math.log(b)))
        dt = jnp.maximum(dt, 1e-4)  # time_step_floor
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)  # softplus^-1
    leaf = jax.random.normal(key, shape, jnp.float32) * a + b
    if kind == "centred":
        leaf = leaf - leaf.mean(-2, keepdims=True)
    return leaf.astype(dtype)


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
