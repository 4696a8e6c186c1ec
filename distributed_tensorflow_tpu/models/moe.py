"""Routed experts on the serving path: a dropless top-k expert layer, used
by ``models/transformer.Block`` in place of its dense MLP where
``cfg.num_experts`` is set, and as the ``E`` layers of a ``layer_pattern``
config.

The layer is told which experts this chip holds (``cfg.held``), routes over
ALL ``num_experts``, and returns the part of the result the held experts
give: a (token, expert) pair whose expert lives elsewhere gives zero here,
and the parts of all the shares add up to the whole layer's routed result.
On one chip that holds every expert nothing is exchanged, and no code
stands in for absent chips. (``parallel/expert_parallel.MoeMlp`` is the
train-only Switch layer with capacity buffers that drop; it is not this
path.)

One implementation for every ``experts_per_token`` k, no switch: the T x k
(token, expert) pairs of the chunk or round are sorted by expert and the
products run over the ragged groups (the kernel ``ops/grouped_matmul.py``;
``TransformerConfig`` refuses widths that do not lie on its tiles); the k
parts of a token are weighted and summed back. The FLOPs are
those of the pairs times ONE expert, the weight bytes those of the experts
that have a pair, whatever the routing, all pairs on one expert included. No
pair is dropped and none is padded to a capacity. An expert is gated SiLU
(``moe_in`` (held, d, 2 * width) holds gate | up) or, with
``cfg.expert_act`` 'relu2', two matrices around relu(.)^2 (``moe_up``
(held, width, d): (out, in) as a checkpoint holds a linear layer, whose last
axis is then d_model's whole lanes whatever the width; ``moe_out`` (held,
width, d) in both). With ``cfg.shared_expert_width`` one more expert
of that width runs on every token as a plain dense product, unweighted;
every share computes it alike.

Two routers, by what the model has, both float32 whatever ``compute_dtype``:

* ``cfg.router_hidden`` > 0, the MLP router (:func:`route`, top-1):
  ``r = h W_d``;
  with the router vector ``r'`` the same token had in the layer before,
  ``r += gamma * r'`` (none in a stage's first layer; the sum is what the
  next layer gets); ``s = W_3 gelu(W_2 gelu(W_1 r))``; ``p = softmax(s)``;
  the expert is ``argmax(p + b)`` with ``b`` the held balancing bias, which
  enters the choice and not the weight ``p[e]``.
* ``cfg.router_hidden`` 0, the linear router (:func:`route_linear`, top-k):
  ``s = sigmoid(h W_r)``; the k largest of ``s + b`` are chosen (the bias
  enters the choice only); their weights are ``cfg.router_scale * s[e] /
  (sum of the k s[e] + 1e-20)``.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from distributed_tensorflow_tpu.ops.grouped_matmul import grouped_matmul

__all__ = ["route", "route_linear", "routed_experts"]


def _f32_dense(cfg, n, name):
    # HIGHEST: an f32 product stays f32 on the TPU too (its default rounds
    # the operands to bfloat16), and a router is a thousandth of a layer.
    return nn.Dense(n, dtype=jnp.float32, use_bias=cfg.use_bias, name=name,
                    precision=jax.lax.Precision.HIGHEST)


def route(mod, cfg, h32, r_prev):
    """(expert id (T,) int32, its probability (T,) f32, router vector (T, R)
    f32) of the tokens ``h32`` (T, d) f32. Layers are declared on ``mod``
    (``router_down``, ``router_gamma``, ``router_w1..3``, ``router_bias``)."""
    r = _f32_dense(cfg, cfg.router_hidden, "router_down")(h32)
    if r_prev is not None:
        gamma = mod.param("router_gamma", nn.initializers.ones,
                          (cfg.router_hidden,))
        r = r + gamma.astype(jnp.float32) * r_prev
    z = nn.gelu(_f32_dense(cfg, cfg.router_hidden, "router_w1")(r),
                approximate=False)
    z = nn.gelu(_f32_dense(cfg, cfg.router_hidden, "router_w2")(z),
                approximate=False)
    scores = _f32_dense(cfg, cfg.num_experts, "router_w3")(z)
    p = jax.nn.softmax(scores, -1)
    bias = mod.param("router_bias", nn.initializers.zeros,
                     (cfg.num_experts,))
    expert = jnp.argmax(p + bias.astype(jnp.float32), -1).astype(jnp.int32)
    weight = jnp.take_along_axis(p, expert[:, None], -1)[:, 0]
    return expert, weight, r


def route_linear(mod, cfg, h32):
    """(expert ids (T, k) int32, their weights (T, k) f32) of the tokens
    ``h32`` (T, d) f32 under the linear sigmoid router (``router`` and
    ``router_bias`` on ``mod``)."""
    s = jax.nn.sigmoid(_f32_dense(cfg, cfg.num_experts, "router")(h32))
    bias = mod.param("router_bias", nn.initializers.zeros,
                     (cfg.num_experts,))
    _, expert = jax.lax.top_k(s + bias.astype(jnp.float32),
                              cfg.experts_per_token)
    picked = jnp.take_along_axis(s, expert, -1)
    weight = cfg.router_scale * picked / (
        picked.sum(-1, keepdims=True) + 1e-20)
    return expert.astype(jnp.int32), weight


def routed_experts(mod, cfg, h32, r_prev=None, mask=None):
    """The MoE sublayer's output for ``h32`` (B, S, d), the normalised
    input in f32: ``(y (B, S, d) f32, as the products accumulated it, router
    vector (B, S, R) f32 (None under the linear router), counts (held,)
    int32)``. ``mask`` (B, S) bool leaves tokens out (a decode round's masked
    lanes, a prefill chunk's padding): their pairs reach no routed expert and
    count for nothing. ``counts`` are the pairs each held expert received.
    Parameters live on ``mod``: the router's, ``moe_in`` (held, d, 2 *
    width: gate | up) or ``moe_up`` (held, width, d), ``moe_out`` (held,
    width, d), and the shared expert's ``shared_in`` / ``shared_out``."""
    b, s, d = h32.shape
    t, k = b * s, cfg.experts_per_token
    held = cfg.held
    n_held, width = len(held), cfg.expert_width
    gated = cfg.expert_act == "swiglu"
    flat = h32.reshape(t, d)
    dt = cfg.compute_dtype

    def act(u, wide):
        if gated:
            return (nn.silu(u[:, :wide]) * u[:, wide:]).astype(dt)
        return jnp.square(nn.relu(u)).astype(dt)

    with jax.named_scope("moe.route"):
        if cfg.router_hidden:
            expert, weight, r = route(
                mod, cfg, flat,
                None if r_prev is None else r_prev.reshape(t, -1))
            r = r.reshape(b, s, -1)
        else:
            expert, weight = route_linear(mod, cfg, flat)
            r = None
        expert, weight = expert.reshape(t * k), weight.reshape(t * k)
        # Local index of each pair's expert; ``n_held`` for an expert that
        # lives elsewhere and for a masked token: sorted behind every group.
        local = np.full(cfg.num_experts, n_held, np.int32)
        local[list(held)] = np.arange(n_held, dtype=np.int32)
        idx = jnp.asarray(local)[expert]
        if mask is not None:
            idx = jnp.where(jnp.repeat(mask.reshape(t), k), idx, n_held)
        order = jnp.argsort(idx, stable=True)
        counts = jnp.zeros(n_held + 1, jnp.int32).at[idx].add(1)[:n_held]
    with jax.named_scope("moe.experts"):
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                            batch_axis=(0,))
        if gated:
            w_in = mod.param("moe_in", init, (n_held, d, 2 * width))
        else:
            w_in = mod.param("moe_up", nn.initializers.lecun_normal(
                in_axis=-1, out_axis=-2, batch_axis=(0,)), (n_held, width, d))
        w_out = mod.param("moe_out", init, (n_held, width, d))
        # Pair i is token i // k.
        xs = flat.astype(dt)[order // k]
        gu = grouped_matmul(xs, w_in.astype(dt), counts,
                            transpose_rhs=not gated)
        ys = grouped_matmul(act(gu, width), w_out.astype(dt), counts)
        # Rows behind the last group belong to no held expert.
        ys = jnp.where((jnp.arange(t * k) < counts.sum())[:, None], ys, 0.0)
        y = jnp.zeros_like(ys).at[order].set(ys) * weight[:, None]
        y = y.reshape(t, k, d).sum(1)
    if cfg.shared_expert_width:
        with jax.named_scope("moe.shared"):
            wide = cfg.shared_expert_width
            dense = lambda n, name: nn.Dense(
                n, dtype=dt, use_bias=cfg.use_bias, name=name)
            u = dense((2 if gated else 1) * wide, "shared_in")(flat.astype(dt))
            y = y + dense(d, "shared_out")(act(u, wide)).astype(jnp.float32)
    return y.reshape(b, s, d), r, counts
