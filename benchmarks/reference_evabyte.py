"""The plain reference of the EvaByte block: the forward pass in
straightforward ``jax.numpy`` and float32, matrix products at ``highest``
precision. It imports nothing of the program, holds no cache and no pages:
windows and chunks are index arithmetic over the whole sequence.

Per layer and head, with s = dh^-1/2, W = ``eva_window``, C = ``eva_chunk``
(the configuration file lists what of this is ``assumed``):

* h = x / sqrt(mean(x^2) + eps) * (1 + g); the residual x stays float32.
* q, k, v = h W_q, h W_k, h W_v (one fused ``qkv`` kernel, equal thirds);
  rotate-half RoPE on q and k.
* for every whole chunk c (positions C c .. C c + C - 1):
  alpha_j = softmax_j(s k_j . phi), k~_c = sum_j alpha_j k_j + mu,
  v~_c = sum_j alpha_j v_j.
* query i attends {(k_j, v_j): j in i's window, j <= i} and {(k~_c, v~_c):
  chunk c lies in a window before i's}, ONE softmax over both.
* x += attn W_o; x += W_down(silu(W_gate h2) * W_up h2), h2 = norm(x).
* final norm, one linear to ``num_pred_heads`` x vocab; head 0 is the next
  byte's.

Attention runs in blocks of query positions (a block never straddles a
window) so that 30k positions fit beside the weights. ``mode`` rounds the
linear layers as ``benchmarks/reference.py`` does (its ``linear``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference import (  # noqa: F401  (re-exported for the runner)
    HI,
    _cfg_items,
    gap_rows,
    linear,
    margin_rows,
    rope,
)

BLOCK = 512  # query positions per attention block, at the most


def rms_norm(x, p, cfg):
    g = p["scale"].astype(jnp.float32)
    y = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True)
                          + float(cfg["norm_eps"]))
    return y * (1.0 + g if cfg.get("norm_unit_offset") else g)


def block_rows(cfg: dict) -> int:
    """Query rows per attention block: the largest divisor of the window
    that is at most ``BLOCK``, so that a block lies in one window."""
    w = int(cfg["eva_window"])
    return max(b for b in range(1, min(BLOCK, w) + 1) if w % b == 0)


def summaries(k, v, phi, mu, chunk: int):
    """k~, v~ (n, H, dh) of the n whole chunks of k, v (T, H, dh)."""
    t, heads, dh = k.shape
    n = t // chunk
    kc = k[: n * chunk].reshape(n, chunk, heads, dh)
    vc = v[: n * chunk].reshape(n, chunk, heads, dh)
    alpha = jax.nn.softmax(
        jnp.einsum("nchd,hd->nch", kc, phi, precision=HI) / dh ** 0.5, 1)
    return (jnp.einsum("nch,nchd->nhd", alpha, kc, precision=HI) + mu,
            jnp.einsum("nch,nchd->nhd", alpha, vc, precision=HI))


def attention(q, k, v, phi, mu, cfg: dict, parts=("mu", "summaries")):
    """EVA attention of one sequence, q, k, v (T, H, dh) rotated, T a whole
    number of blocks. ``parts`` is for the tests that leave a term out."""
    t, heads, dh = q.shape
    w, c = int(cfg["eva_window"]), int(cfg["eva_chunk"])
    blk = block_rows(cfg)
    if "mu" not in parts:
        mu = jnp.zeros_like(mu)
    ks, vs = summaries(k, v, phi, mu, c)
    n = ks.shape[0]
    pad = -t % w
    kp = jnp.pad(k, ((0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, pad), (0, 0), (0, 0)))
    chunk_window = (jnp.arange(n) * c) // w

    def one(i):
        a0 = i * blk
        w0 = (a0 // w) * w
        qb = jax.lax.dynamic_slice(q, (a0, 0, 0), (blk, heads, dh))
        kw = jax.lax.dynamic_slice(kp, (w0, 0, 0), (w, heads, dh))
        vw = jax.lax.dynamic_slice(vp, (w0, 0, 0), (w, heads, dh))
        pos_q = a0 + jnp.arange(blk)
        pos_k = w0 + jnp.arange(w)
        s_w = jnp.einsum("qhd,khd->hqk", qb, kw, precision=HI) / dh ** 0.5
        s_w = jnp.where((pos_k[None, :] <= pos_q[:, None])[None], s_w, -1e30)
        s_s = jnp.einsum("qhd,nhd->hqn", qb, ks, precision=HI) / dh ** 0.5
        earlier = chunk_window[None, :] < (pos_q // w)[:, None]
        if "summaries" not in parts:
            earlier = jnp.zeros_like(earlier)
        s_s = jnp.where(earlier[None], s_s, -1e30)
        p = jax.nn.softmax(jnp.concatenate([s_s, s_w], -1), -1)
        return (jnp.einsum("hqn,nhd->qhd", p[..., :n], vs, precision=HI)
                + jnp.einsum("hqk,khd->qhd", p[..., n:], vw, precision=HI))

    out = jax.lax.map(one, jnp.arange(t // blk))
    return out.reshape(t, heads, dh)


def block(x, p, cfg: dict, mode="f32", parts=("mu", "summaries")):
    """One layer over one sequence x (T, d), T a whole number of blocks."""
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    t, d = x.shape
    heads = int(cfg["num_heads"])
    dh = d // heads
    h = rms_norm(x, p["ln1"], cfg)
    qkv = linear(h, p["qkv"], mode)
    q, k, v = (qkv[:, i * d:(i + 1) * d].reshape(t, heads, dh)
               for i in range(3))
    q = rope(q, float(cfg["rope_theta"]))
    k = rope(k, float(cfg["rope_theta"]))
    attn = attention(q, k, v, p["eva_phi"], p["eva_mu"], cfg, parts)
    x = x + linear(attn.reshape(t, d), p["proj"], mode)
    h = rms_norm(x, p["ln2"], cfg)
    h = jax.nn.silu(linear(h, p["mlp_gate"], mode)) * linear(
        h, p["mlp_up"], mode)
    return x + linear(h, p["mlp_out"], mode)


def head(params, x, cfg: dict, mode="f32"):
    """Every prediction head's logits, (T, num_pred_heads, vocab)."""
    ln = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                params["ln_f"])
    lg = linear(rms_norm(x, ln, cfg), params["lm_head"], mode)
    return lg.reshape(x.shape[0], int(cfg.get("num_pred_heads", 1)),
                      int(cfg["vocab_size"]))


@functools.lru_cache(maxsize=None)
def _jitted(cfg_items, mode, parts):
    cfg = dict(cfg_items)
    return (jax.jit(lambda p, x: block(x, p, cfg, mode, parts)),
            jax.jit(lambda p, x: head(p, x, cfg, mode)))


def logits(params, tokens, cfg: dict, mode="f32", last: int | None = None,
           heads: bool = False, parts=("mu", "summaries")):
    """Head 0's logits (T, vocab) of one sequence in f32, layer by layer so
    that one block's f32 weights are live at a time; every head's (T,
    num_pred_heads, vocab) with ``heads``. ``last`` keeps only the final
    ``last`` positions."""
    blk, hd = _jitted(_cfg_items(cfg), mode, tuple(parts))
    tokens = jnp.asarray(tokens, jnp.int32)
    t = tokens.shape[0]
    padded = jnp.pad(tokens, (0, -t % block_rows(cfg)))
    x = params["tok_embed"]["embedding"][padded].astype(jnp.float32)
    for i in range(int(cfg["num_layers"])):
        x = blk(params[f"block_{i}"], x)
    x = x[:t]
    if last is not None:
        x = x[-last:]
    lg = hd({"ln_f": params["ln_f"], "lm_head": params["lm_head"]}, x)
    return lg if heads else lg[:, 0]
