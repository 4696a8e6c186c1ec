"""Routed experts on the serving path: a dropless top-1 expert layer behind
an MLP router, used by ``models/transformer.Block`` in place of its dense MLP
where ``cfg.num_experts`` is set.

The layer is told which experts this chip holds (``cfg.held``), routes over
ALL ``num_experts``, and returns the part of the result the held experts
give: a token whose expert lives elsewhere gets zero here, and the parts of
all the shares add up to the whole layer's result. On one chip that holds
every expert nothing is exchanged, and no code stands in for absent chips.
(``parallel/expert_parallel.MoeMlp`` is the train-only Switch layer with
capacity buffers that drop; it is not this path.)

One implementation, no switch: the tokens of the chunk or round are sorted
by expert and the three products run over the ragged groups with
``jax.lax.ragged_dot``, which XLA:TPU lowers to its grouped-matmul kernel
(group metadata, then one Mosaic call): the FLOPs are those of the tokens
times ONE expert, the weight bytes those of the experts that have a token,
whatever the routing, all tokens on one expert included. No token is
dropped and none is padded to a capacity.

Router (float32 throughout, whatever ``compute_dtype``): ``r = h W_d``;
with the router vector ``r'`` the same token had in the layer before,
``r += gamma * r'`` (none in a stage's first layer; the sum is what the next
layer gets); ``s = W_3 gelu(W_2 gelu(W_1 r))``; ``p = softmax(s)``; the
expert is ``argmax(p + b)`` with ``b`` the held balancing bias, which enters
the choice and not the weight ``p[e]``.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["route", "routed_experts"]


def route(mod, cfg, h32, r_prev):
    """(expert id (T,) int32, its probability (T,) f32, router vector (T, R)
    f32) of the tokens ``h32`` (T, d) f32. Layers are declared on ``mod``
    (``router_down``, ``router_gamma``, ``router_w1..3``, ``router_bias``)."""
    # HIGHEST: an f32 product stays f32 on the TPU too (its default rounds
    # the operands to bfloat16), and these four are a thousandth of a layer.
    dense = lambda n, name: nn.Dense(
        n, dtype=jnp.float32, use_bias=cfg.use_bias, name=name,
        precision=jax.lax.Precision.HIGHEST)
    r = dense(cfg.router_hidden, "router_down")(h32)
    if r_prev is not None:
        gamma = mod.param("router_gamma", nn.initializers.ones,
                          (cfg.router_hidden,))
        r = r + gamma.astype(jnp.float32) * r_prev
    z = nn.gelu(dense(cfg.router_hidden, "router_w1")(r), approximate=False)
    z = nn.gelu(dense(cfg.router_hidden, "router_w2")(z), approximate=False)
    scores = dense(cfg.num_experts, "router_w3")(z)
    p = jax.nn.softmax(scores, -1)
    bias = mod.param("router_bias", nn.initializers.zeros,
                     (cfg.num_experts,))
    expert = jnp.argmax(p + bias.astype(jnp.float32), -1).astype(jnp.int32)
    weight = jnp.take_along_axis(p, expert[:, None], -1)[:, 0]
    return expert, weight, r


def routed_experts(mod, cfg, h32, r_prev=None, mask=None):
    """The MoE sublayer's output for ``h32`` (B, S, d), the normalised
    input in f32: ``(y (B, S, d) f32, as the products accumulated it, router
    vector (B, S, R) f32, counts (held,) int32)``. ``mask`` (B, S) bool leaves tokens out
    (a decode round's masked lanes, a prefill chunk's padding): they reach no
    expert, count for nothing and get zero. ``counts`` are the tokens each
    held expert received. Parameters live on ``mod``: the router's, and
    ``moe_in`` (held, d, 2 * width: gate | up) and ``moe_out`` (held, width,
    d)."""
    b, s, d = h32.shape
    t = b * s
    held = cfg.held
    n_held, width = len(held), cfg.expert_width
    flat = h32.reshape(t, d)
    with jax.named_scope("moe.route"):
        expert, weight, r = route(
            mod, cfg, flat,
            None if r_prev is None else r_prev.reshape(t, -1))
        # Local index of each token's expert; ``n_held`` for an expert that
        # lives elsewhere and for a masked token: sorted behind every group.
        local = np.full(cfg.num_experts, n_held, np.int32)
        local[list(held)] = np.arange(n_held, dtype=np.int32)
        idx = jnp.asarray(local)[expert]
        if mask is not None:
            idx = jnp.where(mask.reshape(t), idx, n_held)
        order = jnp.argsort(idx, stable=True)
        counts = jnp.zeros(n_held + 1, jnp.int32).at[idx].add(1)[:n_held]
    with jax.named_scope("moe.experts"):
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                            batch_axis=(0,))
        w_in = mod.param("moe_in", init, (n_held, d, 2 * width))
        w_out = mod.param("moe_out", init, (n_held, width, d))
        dt = cfg.compute_dtype
        xs = flat.astype(dt)[order]
        gu = jax.lax.ragged_dot(xs, w_in.astype(dt), counts,
                                preferred_element_type=jnp.float32)
        act = (nn.silu(gu[:, :width]) * gu[:, width:]).astype(dt)
        ys = jax.lax.ragged_dot(act, w_out.astype(dt), counts,
                                preferred_element_type=jnp.float32)
        # Rows behind the last group belong to no held expert.
        ys = jnp.where((jnp.arange(t) < counts.sum())[:, None], ys, 0.0)
        y = jnp.zeros_like(ys).at[order].set(ys) * weight[:, None]
    return y.reshape(b, s, d), r.reshape(b, s, -1), counts
