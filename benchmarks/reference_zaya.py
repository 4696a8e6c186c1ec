"""The plain reference of the ZAYA1 block: the forward pass in
straightforward ``jax.numpy`` and float32, matrix products at ``highest``
precision. It imports nothing of the program, holds no cache, no pages and
no state: convolutions and the value shift are index arithmetic over the
whole sequence, and the experts are a loop with a mask over the held ones.

A layer is x <- x + CCA(norm(x)); x <- x + MoE(norm(x)), norm = RMSNorm with
a learned weight; after the last layer a final norm and logits = h E^T with
E the embedding. With H query heads, G kv heads of dh (group H/G), taps t0,
t1 (the configuration file lists what of this is ``assumed``):

CCA(h): [q~ | k~ | va | vb] = h W_in (H dh, G dh, (G - G/2) dh, (G/2) dh);
v_t = [va_t ; vb_{t-1}] (vb_{-1} = 0); z = [q~ | k~];
a_t[c] = sum_{j<t0} w0[c, j] z_{t-j}[c];
b_t[n] = sum_{j<t1} a_{t-j}[n] W1[n, j] (per head n of the H + G);
q_t[n] = b_t[n] + (q~_t[n] + k~_t[g(n)]) / 2;
k_t[g] = b_t[H + g] + (mean_{n in g} q~_t[n] + k~_t[g]) / 2;
every head scaled to length sqrt(dh), k_t[g] times tau_g; rotate-half RoPE
on the first ``rope_fraction`` x dh dimensions; causal softmax(q k^T /
sqrt(dh)) v over the G kv heads; times W_O.

MoE(h): r = h W_d (+ gamma * r' of the layer before, none in the first);
s = W_3 gelu(W_2 gelu(W_1 r)) (exact gelu); p = softmax(s);
e = argmax(p + b); y = p[e] * W_down^e (silu(h W_gate^e) * (h W_up^e)), for
the experts held (``experts_held``, all by default; a token whose expert is
not held gets 0). The router is float32 in every mode.

``mode`` rounds the linear layers (``W_in``, ``W_O``, the experts, the head)
as ``benchmarks/reference.py`` does. Three more modes are float32 with a
routing fault (``ROUTING_FAULTS``), the controls that say what of the router
the cell's comparison can see: ``"wrong_expert"`` sends every token to its
SECOND-best expert; ``"later_router_bf16"`` rounds the router's input and
down-projection to bfloat16 in every layer after the first (where the
configuration states float32); ``"later_router_zero"`` drops that
down-projection's term there altogether, leaving the vector carried from the
layer before.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference import HI, _cfg_items, linear
from benchmarks.reference import gap_rows as _gap_rows
from benchmarks.reference import margin_rows as _margin_rows
from benchmarks.weights_zaya import held_experts

ROUTING_FAULTS = ("wrong_expert", "later_router_bf16", "later_router_zero")


def _linear_mode(mode):
    """The rounding of the linear layers under ``mode``: none under a
    routing fault."""
    return "f32" if mode in ROUTING_FAULTS else mode


def rms_norm(x, p, cfg):
    y = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True)
                          + float(cfg["norm_eps"]))
    return y * p["scale"].astype(jnp.float32)


def shift(x, j):
    """x (T, ...) delayed by j positions, zeros before position 0."""
    return x if j == 0 else jnp.pad(x, ((j, 0),) + ((0, 0),) * (x.ndim - 1))[
        : x.shape[0]]


def partial_rope(x, theta, rot):
    """x (T, n, dh): rotate-half RoPE on the first ``rot`` dimensions."""
    t = x.shape[0]
    half = rot // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], -1)


def unit_heads(x, dh):
    return x * (dh ** 0.5 / jnp.sqrt((x * x).sum(-1, keepdims=True) + 1e-6))


def cca(h, p, cfg, mode):
    t = h.shape[0]
    heads, kv, dh = (int(cfg[k]) for k in (
        "num_heads", "num_kv_heads", "head_dim"))
    group = heads // kv
    cq, ck, cvb = heads * dh, kv * dh, (kv // 2) * dh
    lin = _linear_mode(mode)
    z = linear(h, p["cca_in"], lin)
    q_lat, k_lat = z[:, :cq], z[:, cq:cq + ck]
    va, vb = z[:, cq + ck:cq + 2 * ck - cvb], z[:, cq + 2 * ck - cvb:]
    v = jnp.concatenate([va, shift(vb, 1)], -1).reshape(t, kv, dh)
    lat = z[:, :cq + ck]
    a = sum(shift(lat, j) * p["cca_conv0"][:, j]
            for j in range(int(cfg["cca_time0"])))
    a = a.reshape(t, heads + kv, dh)
    b = sum(jnp.einsum("thd,hde->the", shift(a, j), p["cca_conv1"][:, j],
                       precision=HI) for j in range(int(cfg["cca_time1"])))
    q4 = q_lat.reshape(t, kv, group, dh)
    k4 = k_lat.reshape(t, kv, dh)
    q = b[:, :heads].reshape(t, kv, group, dh) + (q4 + k4[:, :, None]) / 2
    k = b[:, heads:] + (q4.mean(2) + k4) / 2
    q = unit_heads(q, dh).reshape(t, heads, dh)
    k = unit_heads(k, dh) * p["cca_temp"][:, None]
    if cfg.get("position", "learned") == "rope":
        rot = int(round(float(cfg.get("rope_fraction", 1.0)) * dh))
        q = partial_rope(q, float(cfg["rope_theta"]), rot)
        k = partial_rope(k, float(cfg["rope_theta"]), rot)
    scores = jnp.einsum("tkgd,skd->kgts", q.reshape(t, kv, group, dh), k,
                        precision=HI) / dh ** 0.5
    pos = jnp.arange(t)
    scores = jnp.where((pos[None, :] <= pos[:, None])[None, None], scores,
                       -1e30)
    attn = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(scores, -1), v,
                      precision=HI)
    return linear(attn.reshape(t, cq), p["proj"], lin)


def router(h, r_prev, p, mode="f32"):
    """(softmax probabilities (T, experts), the router vector (T, R)) in
    float32, whatever the rounding of the linear layers; ``mode`` matters
    only where it names a fault of the later layers' down-projection."""
    dense = lambda x, name: jnp.matmul(x, p[name]["kernel"], precision=HI)
    if r_prev is None:
        r = dense(h, "router_down")
    elif mode == "later_router_zero":
        r = p["router_gamma"] * r_prev
    elif mode == "later_router_bf16":
        r = linear(h, p["router_down"], "bf16") + p["router_gamma"] * r_prev
    else:
        r = dense(h, "router_down") + p["router_gamma"] * r_prev
    s = dense(jax.nn.gelu(dense(jax.nn.gelu(dense(
        r, "router_w1"), approximate=False), "router_w2"),
        approximate=False), "router_w3")
    return jax.nn.softmax(s, -1), r


def moe(h, r_prev, p, cfg, mode, held):
    """(the held experts' part of the layer's output, the router vector)."""
    prob, r = router(h, r_prev, p, mode)
    _, order = jax.lax.top_k(prob + p["router_bias"], 2)
    expert = order[:, 1] if mode == "wrong_expert" else order[:, 0]
    weight = jnp.take_along_axis(prob, expert[:, None], -1)
    lin = _linear_mode(mode)
    width = int(cfg["expert_width"])
    y = jnp.zeros_like(h)
    for i, e in enumerate(held):
        gu = linear(h, {"kernel": p["moe_in"][i]}, lin)
        out = linear(jax.nn.silu(gu[:, :width]) * gu[:, width:],
                     {"kernel": p["moe_out"][i]}, lin)
        y = y + jnp.where((expert == e)[:, None], out, 0.0)
    return y * weight, r


def block(x, r_prev, p, cfg: dict, mode="f32", held=()):
    """One layer over one sequence x (T, d): (x, router vector)."""
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    x = x + cca(rms_norm(x, p["ln1"], cfg), p, cfg, mode)
    y, r = moe(rms_norm(x, p["ln2"], cfg), r_prev, p, cfg, mode, held)
    return x + y, r


def head(params, x, cfg: dict, mode="f32"):
    ln = {"scale": params["ln_f"]["scale"].astype(jnp.float32)}
    return linear(rms_norm(x, ln, cfg),
                  {"kernel": params["tok_embed"]["embedding"].T},
                  _linear_mode(mode))


@functools.lru_cache(maxsize=None)
def _jitted(cfg_items, mode, held):
    cfg = dict(cfg_items)
    return (jax.jit(lambda p, x: block(x, None, p, cfg, mode, held)),
            jax.jit(lambda p, x, r: block(x, r, p, cfg, mode, held)),
            jax.jit(lambda p, x: head(p, x, cfg, mode)))


def hidden(params, tokens, cfg: dict, mode="f32"):
    """The last layer's output (T, d) before the final norm, of one
    sequence, layer by layer so that one layer's f32 weights are live at a
    time."""
    first, later, _ = _jitted(_cfg_items(cfg), mode or "f32",
                              held_experts(cfg))
    tokens = jnp.asarray(tokens, jnp.int32)
    x = params["tok_embed"]["embedding"][tokens].astype(jnp.float32)
    r = None
    for i in range(int(cfg["num_layers"])):
        p = params[f"block_{i}"]
        x, r = first(p, x) if r is None else later(p, x, r)
    return x


def logits(params, tokens, cfg: dict, mode="f32", last: int | None = None):
    """Logits (T, vocab) of one sequence in f32, layer by layer so that one
    layer's f32 weights are live at a time. ``last`` keeps only the final
    ``last`` positions."""
    hd = _jitted(_cfg_items(cfg), mode or "f32", held_experts(cfg))[2]
    x = hidden(params, tokens, cfg, mode)
    if last is not None:
        x = x[-last:]
    return hd({"ln_f": params["ln_f"], "tok_embed": params["tok_embed"]}, x)


class Rows:
    """One sequence's logits, formed ``BLOCK`` rows at a time: at 262,272
    ids the whole (T, vocab) array of a 3,584-token request is 3.8 GB, which
    does not fit beside the weights twice. What the runner asks of logits
    is here: :func:`gap_rows`, :func:`margin_rows` and ``argmax``."""

    BLOCK = 512

    def __init__(self, params, tokens, cfg: dict, mode="f32"):
        self.x = hidden(params, tokens, cfg, mode)
        self._head = functools.partial(
            _jitted(_cfg_items(cfg), mode or "f32", held_experts(cfg))[2],
            {"ln_f": params["ln_f"], "tok_embed": params["tok_embed"]})

    def over_blocks(self, fn, *rows):
        """``fn(logits block, *the same rows of every array in rows)``,
        concatenated over the blocks."""
        n = self.x.shape[0]
        return jnp.concatenate([
            fn(self._head(self.x[a:a + self.BLOCK]),
               *(r[a:a + self.BLOCK] for r in rows))
            for a in range(0, n, self.BLOCK)])

    def argmax(self, axis=-1):
        return self.over_blocks(lambda lg: lg.argmax(-1))


def gap_rows(lg, tokens):
    """``reference.gap_rows`` over whole logits or over :class:`Rows`."""
    if isinstance(lg, Rows):
        return lg.over_blocks(_gap_rows, jnp.asarray(tokens))
    return _gap_rows(lg, tokens)


def margin_rows(lg):
    """``reference.margin_rows`` over whole logits or over :class:`Rows`."""
    if isinstance(lg, Rows):
        return lg.over_blocks(_margin_rows)
    return _margin_rows(lg)
