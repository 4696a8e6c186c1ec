"""ZAYA1's weights from ``--seed``, made on the device in one jitted call.

The tree has the names the program's ``TransformerLM`` expects of a config
with ``cca_time0`` / ``cca_time1``, ``num_experts`` and ``tie_embeddings``
(no ``lm_head``: the head is the embedding); the plain reference
(``reference_zaya.py``) reads the same tree. Kernels are normal with standard
deviation 1/sqrt(fan_in) and the embedding 1/sqrt(d_model); the norms'
weights, the key temperatures and the depth-averaging gammas are 1 + normal
x 0.05 / 0.1 / 0.1; the convolutions normal x (taps x channels mixed)^-1/2.
Every vector is non-zero so that a dropped term shows in the logits.

**The router is drawn as training leaves one: confident, balanced, and
steady under rounding.** With every weight at 1/sqrt(fan_in) a random deep
network's attention is nearly uniform, its output is the mean value of the
context, and after a few layers the hidden states of all tokens share one
large common part. A router that reads those alone sends 72% of a round's
tokens to one expert and touches 8 of 16 experts a layer (my chip run, PR 35,
the first). EVEN routing is what this draw ASSUMES of a trained ZAYA1 (32
tokens over 16 evenly loaded experts touch 14.0, ISSUE 35's arithmetic); no
passage of the technical report that states the trained model's load balance
is on this machine to cite, so the share of experts a round touches is this
draw's, not the model's (PERF.md §7.9).
Sharpening attention instead (key temperatures of 4) evens the routing
(13 of 16) and makes the random network chaotic: a bfloat16 rounding then
flips a route somewhere in 20 layers for nearly every token and 97% of the
served tokens stop being the float32 reference's best (my chip run, PR 35,
the second), which no trained model does. So the draw keeps the network as
it is and uses the router's own depth averaging: the first layer's router
vector, made from a hidden state that is still the token's own, is carried
to every layer with gammas of 1 + normal x 0.1, and the later layers' own
down-projections are drawn at a TENTH of 1/sqrt(fan_in), so that every
layer's choice follows the token through its own MLP. Also: the router's last
matrix is FOUR times wider than 1/sqrt(fan_in), so that a token's best expert
carries a weight of a third to a half rather than the 1/16 of a flat softmax
(with a flat one the experts would be a sixteenth of what they are in the
model and a wrong route would not show); the columns of the router's last two
matrices have their means taken out (a GELU's output has a positive mean,
which through random columns favours the same few experts for every token);
the balancing biases are normal x 0.02, drawn and not trained. None of this
is a mechanism: the program and the reference compute the published
equations on whatever tree they are handed. What it costs the comparison:
the later layers' own down-projections carry a tenth of the score, so a fault
in THEM shows only as far as the reference's controls ``later_router_zero``
and ``later_router_bf16`` say it does (PERF.md §2).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.weights import seed_key


def held_experts(cfg: dict) -> tuple:
    held = cfg.get("experts_held")
    return tuple(range(int(cfg["num_experts"]))) if held is None else tuple(
        int(e) for e in held)


def leaf_shapes(cfg: dict) -> dict:
    """{path tuple: (shape, std, mean)} for every parameter."""
    d = int(cfg["d_model"])
    heads, kv = int(cfg["num_heads"]), int(cfg["num_kv_heads"])
    dh = int(cfg["head_dim"])
    t0, t1 = int(cfg["cca_time0"]), int(cfg["cca_time1"])
    rh, width = int(cfg["router_hidden"]), int(cfg["expert_width"])
    n_exp, n_held = int(cfg["num_experts"]), len(held_experts(cfg))
    out = {("tok_embed", "embedding"): (
        (int(cfg["vocab_size"]), d), d ** -0.5, 0.0)}

    def dense(prefix, fan_in, fan_out, gain=1.0):
        out[prefix + ("kernel",)] = (
            (fan_in, fan_out), gain * fan_in ** -0.5, 0.0)

    for i in range(int(cfg["num_layers"])):
        b = (f"block_{i}",)
        out[b + ("ln1", "scale")] = ((d,), 0.05, 1.0)
        dense(b + ("cca_in",), d, (heads + 2 * kv) * dh)
        out[b + ("cca_conv0",)] = (((heads + kv) * dh, t0), t0 ** -0.5, 0.0)
        out[b + ("cca_conv1",)] = (
            (heads + kv, t1, dh, dh), (t1 * dh) ** -0.5, 0.0)
        out[b + ("cca_temp",)] = ((kv,), 0.1, 1.0)
        dense(b + ("proj",), heads * dh, d)
        out[b + ("ln2", "scale")] = ((d,), 0.05, 1.0)
        dense(b + ("router_down",), d, rh, gain=0.1 if i else 1.0)
        if i:  # depth averaging: none in the stage's first layer
            out[b + ("router_gamma",)] = ((rh,), 0.1, 1.0)
        dense(b + ("router_w1",), rh, rh)
        dense(b + ("router_w2",), rh, rh)
        dense(b + ("router_w3",), rh, n_exp, gain=4.0)
        out[b + ("router_bias",)] = ((n_exp,), 0.02, 0.0)
        out[b + ("moe_in",)] = ((n_held, d, 2 * width), d ** -0.5, 0.0)
        out[b + ("moe_out",)] = (
            (n_held, width, d), width ** -0.5, 0.0)
    out[("ln_f", "scale")] = ((d,), 0.05, 1.0)
    return out


def make_params(cfg: dict, seed: int, dtype):
    """The whole tree in one jitted call, in ``dtype``."""
    shapes = leaf_shapes(cfg)

    def build(key):
        tree: dict = {}
        for n, (path, (shape, std, mean)) in enumerate(shapes.items()):
            leaf = jax.random.normal(jax.random.fold_in(key, n), shape,
                                     jnp.float32) * std + mean
            if path[-2:-1] in (("router_w2",), ("router_w3",)):
                leaf = leaf - leaf.mean(0, keepdims=True)
            node = tree
            for part in path[:-1]:
                node = node.setdefault(part, {})
            node[path[-1]] = leaf.astype(dtype)
        return tree

    return jax.jit(build)(seed_key(seed))

