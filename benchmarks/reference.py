"""The plain reference: a decoder-only transformer in straightforward
``jax.numpy`` and float32, matrix products at ``highest`` precision.

It imports nothing of the program. It follows the published block (pre-norm
LayerNorm, fused q|k|v projection with grouped kv heads, rotary or learned
positions, causal attention under an optional sliding window, tanh GELU,
biases) with the departures each configuration file lists (LayerNorm epsilon
1e-6, untied head). It reads the parameter tree the benchmark itself made
from the seed (``benchmarks/weights.py``).

``mode`` selects the precision of the linear layers: ``"f32"`` (the
reference), ``"bf16"`` (operands rounded to bfloat16), ``"int8"`` (weights
rounded to int8 by output channel, activations by row, products exact) or
``"fp8"`` (the same scaling, operands rounded to float8 e4m3): the controls
of step 2 of "How correct is decided".
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

EPS = 1e-6
HI = jax.lax.Precision.HIGHEST


def layer_norm(x, p):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + EPS) * p["scale"] + p["bias"]


def _q8(a, axis):
    scale = jnp.maximum(jnp.max(jnp.abs(a), axis=axis, keepdims=True),
                        1e-30) / 127.0
    return jnp.round(a / scale), scale


def linear(x, p, mode="f32"):
    w = p["kernel"].astype(jnp.float32)
    if mode == "int8":
        xq, xs = _q8(x, -1)
        wq, ws = _q8(w, 0)
        y = jnp.matmul(xq.astype(jnp.int8), wq.astype(jnp.int8),
                       preferred_element_type=jnp.int32)
        y = y.astype(jnp.float32) * xs * ws
    elif mode == "fp8":
        f8 = lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        xs = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True), 1e-30) / 448.0
        ws = jnp.maximum(jnp.max(jnp.abs(w), 0, keepdims=True), 1e-30) / 448.0
        y = jnp.matmul(f8(x / xs), f8(w / ws), precision=HI) * xs * ws
    elif mode == "bf16":
        y = jnp.matmul(x.astype(jnp.bfloat16).astype(jnp.float32),
                       w.astype(jnp.bfloat16).astype(jnp.float32),
                       precision=HI)
    else:
        y = jnp.matmul(x, w, precision=HI)
    if "bias" in p:
        y = y + p["bias"].astype(jnp.float32)
    return y


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def rope(x, theta):
    """x (T, n, dh): rotate halves (the GPT-NeoX / HF convention)."""
    t, _, dh = x.shape
    half = dh // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block(x, p, cfg: dict, mode="f32"):
    """One pre-norm block over one sequence x (T, d)."""
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    t, d = x.shape
    heads = int(cfg["num_heads"])
    kv = int(cfg.get("num_kv_heads") or heads)
    dh = d // heads
    h = layer_norm(x, p["ln1"])
    qkv = linear(h, p["qkv"], mode)
    q = qkv[:, :d].reshape(t, heads, dh)
    k = qkv[:, d:d + kv * dh].reshape(t, kv, dh)
    v = qkv[:, d + kv * dh:].reshape(t, kv, dh)
    if cfg.get("position", "learned") == "rope":
        q = rope(q, float(cfg["rope_theta"]))
        k = rope(k, float(cfg["rope_theta"]))
    group = heads // kv
    qg = q.reshape(t, kv, group, dh)
    scores = jnp.einsum("tkgd,skd->kgts", qg, k, precision=HI) / dh ** 0.5
    pos = jnp.arange(t)
    allowed = pos[None, :] <= pos[:, None]
    w = cfg.get("attention_window")
    if w:
        allowed &= pos[None, :] > pos[:, None] - int(w)
    scores = jnp.where(allowed[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, -1)
    attn = jnp.einsum("kgts,skd->tkgd", probs, v, precision=HI)
    x = x + linear(attn.reshape(t, d), p["proj"], mode)
    h = layer_norm(x, p["ln2"])
    h = gelu_tanh(linear(h, p["mlp_in"], mode))
    return x + linear(h, p["mlp_out"], mode)


def embed(params, tokens, cfg: dict):
    x = params["tok_embed"]["embedding"][tokens].astype(jnp.float32)
    if cfg.get("position", "learned") == "learned":
        pe = params["pos_embed"]["embedding"][: tokens.shape[0]]
        x = x + pe.astype(jnp.float32)
    return x


def head(params, x, mode="f32"):
    ln = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                params["ln_f"])
    return linear(layer_norm(x, ln), params["lm_head"], mode)


@functools.lru_cache(maxsize=None)
def _jitted(cfg_items, mode):
    cfg = dict(cfg_items)
    return (jax.jit(lambda p, tok: embed(p, tok, cfg)),
            jax.jit(lambda x, p: block(x, p, cfg, mode)),
            jax.jit(lambda p, x: head(p, x, mode)))


def logits(params, tokens, cfg: dict, mode="f32", last: int | None = None):
    """Logits (f32) of one sequence, layer by layer so that only one
    block's f32 weights are live at a time. ``last`` keeps only the final
    ``last`` positions' logits."""
    emb, blk, hd = _jitted(_cfg_items(cfg), mode)
    x = emb({k: params[k] for k in params if k.endswith("_embed")},
            jnp.asarray(tokens, jnp.int32))
    for i in range(int(cfg["num_layers"])):
        x = blk(x, params[f"block_{i}"])
    if last is not None:
        x = x[-last:]
    return hd({"ln_f": params["ln_f"], "lm_head": params["lm_head"]}, x)


@jax.jit
def gap_rows(lg, tokens):
    """At every row, how far the logit of ``tokens[row]`` lies below the
    row's best."""
    got = jnp.take_along_axis(lg, tokens.astype(jnp.int32)[:, None], -1)
    return lg.max(-1) - got[:, 0]


@jax.jit
def margin_rows(lg):
    """At every row, the reference's best logit minus its second best."""
    top = jax.lax.top_k(lg, 2)[0]
    return top[:, 0] - top[:, 1]


def _row_loss(hp, x, tok, mode):
    lp = jax.nn.log_softmax(head(hp, x, mode)[:-1], -1)
    return -jnp.take_along_axis(lp, tok[1:, None], -1)[:, 0].mean()


@functools.lru_cache(maxsize=None)
def _jitted_grads(cfg_items, mode):
    cfg = dict(cfg_items)
    blk = lambda x, p: block(x, p, cfg, mode)

    def blk_vjp(x, p, g):
        return jax.vjp(blk, x, p)[1](g)

    def emb_vjp(ep, tok, g):
        return jax.vjp(lambda e: embed(e, tok, cfg), ep)[1](g)[0]

    return (jax.jit(lambda ep, tok: embed(ep, tok, cfg)), jax.jit(blk),
            jax.jit(blk_vjp), jax.jit(emb_vjp),
            jax.jit(jax.value_and_grad(
                lambda hp, x, tok: _row_loss(hp, x, tok, mode),
                argnums=(0, 1))),
            jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b)))


def _cfg_items(cfg: dict):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))
                        or v is None))


def loss_and_grads(params, tokens, cfg: dict, mode="f32"):
    """Mean next-token cross-entropy over a batch (B, S) and its gradient,
    by hand-driven back-propagation: one row at a time and one block at a
    time (each block's forward is recomputed inside its own vjp), so that
    the reference compiles a single block and fits beside nothing else."""
    emb, blk, blk_vjp, emb_vjp, head_grad, add = _jitted_grads(
        _cfg_items(cfg), mode)
    n = int(cfg["num_layers"])
    ep = {k: params[k] for k in params if k.endswith("_embed")}
    hp = {"ln_f": params["ln_f"], "lm_head": params["lm_head"]}
    total, acc = 0.0, None
    rows = tokens.shape[0]
    for b in range(rows):
        tok = jnp.asarray(tokens[b], jnp.int32)
        xs = [emb(ep, tok)]
        for i in range(n):
            xs.append(blk(xs[-1], params[f"block_{i}"]))
        loss, (g_head, gx) = head_grad(hp, xs.pop(), tok)
        grads = dict(g_head)
        for i in reversed(range(n)):
            gx, grads[f"block_{i}"] = blk_vjp(
                xs.pop(), params[f"block_{i}"], gx)
        grads.update(emb_vjp(ep, tok, gx))
        acc = grads if acc is None else add(acc, grads)
        total += float(loss)
    scale = 1.0 / rows
    return total * scale, jax.tree_util.tree_map(lambda g: g * scale, acc)
