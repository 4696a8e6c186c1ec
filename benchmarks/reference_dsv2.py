"""The plain reference of the DeepSeek-V2-Lite stage: the forward pass in
straightforward ``jax.numpy`` and float32, matrix products at ``highest``
precision. It imports nothing of the program, holds no cache, no page and no
kernel, takes one sequence at a time and attends in the EXPANDED form only
(every head's K and V made from the latent: the program's absorbed decode is
thereby checked against the other form).

``x_0 = E[token]``. Layer i is ``x <- x + MLA(RMSNorm(x))``, then ``x <- x +
MLP(RMSNorm(x))``: the dense SwiGLU of ``d_ff`` in layer 0 (``dense_layers``
1), the expert layer behind it; a final RMSNorm and ``logits = h W_head``
(its own matrix). Every RMSNorm has epsilon ``norm_eps`` and a learned
weight, no unit offset.

MLA (DeepSeek-V2, arXiv:2405.04434 section 2.1; no q-LoRA), H heads: ``q =
h W_q``, a head [q_nope | q_pe]; ``[c | k_pe] = h W_kva``, ``c <-
RMSNorm(c)``; ``[k_nope | v]_h = c W_kvb,h``; q_pe and k_pe (one a position,
shared by the heads) go from interleaved pairs to halves (DeepSeek-V2's
``apply_rotary_pos_emb``) and turn by YaRN's tables: frequencies ``inter =
extra / factor`` and ``extra = theta^(-2i/d)`` mixed by a ramp between
``floor(dim(beta_fast))`` and ``ceil(dim(beta_slow))``, ``dim(b) = d
ln(original / (2 pi b)) / (2 ln theta)``, the tables times ``m(mscale) /
m(mscale_all_dim)``; logit ``(q_nope . k_nope + q_pe . k_pe) * m(mscale_all_
dim)^2 / sqrt(nope + rope)`` with ``m(x) = 0.1 x ln(factor) + 1``; causal
softmax, ``o W_o``. Attention runs ``QUERY_BLOCK`` query rows at a time (a
28k-position request's scores do not fit whole).

The expert layer (DeepSeekMoE, section 2.2): ``s = softmax(h W_r)`` over
all experts in float32; the k largest (greedy); weights ``scale * s[e]``,
not renormalised; ``sum_e w[e] SwiGLU_e(h)`` over the experts held, by a
mask over experts taken ``EXPERT_BLOCK`` at a time, plus the shared experts
as one SwiGLU of their summed width on every token, unweighted.

``mode`` rounds the linear layers as ``benchmarks/reference.py`` does (the
router is float32 in every mode). Five more modes are float32 with a fault
(``FAULTS``), the controls that say what the cell's comparison can see:
``"wrong_expert"`` sends every token's k pairs to the experts ranked k+1 to
2k; ``"no_shared"`` drops the shared experts; ``"plain_rope"`` turns the
rope part by plain RoPE frequencies (YaRN off, tables unscaled);
``"no_mscale"`` scales the logits by 1/sqrt(nope + rope) alone;
``"no_rope_key"`` drops the q_pe . k_pe term.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import HI, _cfg_items, linear
from benchmarks.reference_nemotron import Rows as _Rows
from benchmarks.reference_nemotron import gap_rows, margin_rows

FAULTS = ("wrong_expert", "no_shared", "plain_rope", "no_mscale",
          "no_rope_key")
EXPERT_BLOCK = 8
QUERY_BLOCK = 256
ROW_BLOCK = 4096

__all__ = ["FAULTS", "Rows", "logits", "hidden", "gap_rows", "margin_rows",
           "yarn_inv_freq", "route"]


def _linear_mode(mode):
    return "f32" if mode in FAULTS else mode


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _m(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: dict) -> np.ndarray:
    """(rope/2,) float64: YaRN's frequencies as DeepSeek-V2 computes them."""
    d, theta = int(cfg["qk_rope_head_dim"]), float(cfg["rope_theta"])
    factor = float(cfg["yarn_factor"])
    orig = float(cfg["yarn_original_len"])
    extra = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)

    def dim(rot):
        return d * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim(float(cfg["yarn_beta_fast"]))), 0)
    high = min(math.ceil(dim(float(cfg["yarn_beta_slow"]))), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return extra / factor * (1.0 - mask) + extra * mask


def _rope_tables(t: int, cfg: dict, mode):
    d, theta = int(cfg["qk_rope_head_dim"]), float(cfg["rope_theta"])
    factor = float(cfg.get("yarn_factor") or 0.0)
    if factor and mode != "plain_rope":
        inv = yarn_inv_freq(cfg)
        scale = (_m(factor, float(cfg["yarn_mscale"]))
                 / _m(factor, float(cfg["yarn_mscale_all_dim"])))
    else:
        inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
        scale = 1.0
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None]
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def _rotate(x, cos, sin):
    """x (T, n, d) in interleaved pairs: to halves, then rotate-half."""
    t, n, d = x.shape
    x = x.reshape(t, n, d // 2, 2).swapaxes(-1, -2).reshape(t, n, d)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    c, s = cos[:, None], sin[:, None]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _logit_scale(cfg: dict, mode) -> float:
    base = (int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])) ** -0.5
    factor = float(cfg.get("yarn_factor") or 0.0)
    mall = float(cfg.get("yarn_mscale_all_dim") or 0.0)
    if mode == "no_mscale" or not factor or not mall:
        return base
    return base * _m(factor, mall) ** 2


def mla(h, p, cfg: dict, mode):
    """Latent attention of one sequence h (T, d), expanded."""
    t = h.shape[0]
    heads = int(cfg["num_heads"])
    r, nope, rope, vd = (int(cfg[k]) for k in (
        "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    lin = _linear_mode(mode)
    q = linear(h, {"kernel": p["q_proj"]}, lin).reshape(t, heads, nope + rope)
    kva = linear(h, {"kernel": p["kv_a"]}, lin)
    c = rms_norm(kva[:, :r], p["kv_norm"]["scale"], float(cfg["norm_eps"]))
    kv = linear(c, {"kernel": p["kv_b"].reshape(r, -1)}, lin).reshape(
        t, heads, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    cos, sin = _rope_tables(t, cfg, mode)
    q_pe = _rotate(q[..., nope:], cos, sin)
    k_pe = _rotate(kva[:, None, r:], cos, sin)[:, 0]
    q_nope = q[..., :nope]
    scale = _logit_scale(cfg, mode)
    qb = min(QUERY_BLOCK, t)
    pad = -t % qb
    qn = jnp.pad(q_nope, ((0, pad), (0, 0), (0, 0))).reshape(-1, qb, heads, nope)
    qp = jnp.pad(q_pe, ((0, pad), (0, 0), (0, 0))).reshape(-1, qb, heads, rope)
    keys = jnp.arange(t)

    def one(args):
        i, qn_b, qp_b = args
        sc = jnp.einsum("qhd,khd->hqk", qn_b, k_nope, precision=HI)
        if mode != "no_rope_key":
            sc = sc + jnp.einsum("qhd,kd->hqk", qp_b, k_pe, precision=HI)
        rows = i * qb + jnp.arange(qb)
        sc = jnp.where((keys[None, :] <= rows[:, None])[None], sc * scale,
                       -1e30)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v,
                          precision=HI)

    out = jax.lax.map(one, (jnp.arange(qn.shape[0]), qn, qp))
    out = out.reshape(-1, heads * vd)[:t]
    return linear(out, {"kernel": p["o_proj"]}, lin)


def _swiglu(h, w_in, w_out, lin):
    u = linear(h, {"kernel": w_in}, lin)
    wide = u.shape[-1] // 2
    return linear(jax.nn.silu(u[:, :wide]) * u[:, wide:], {"kernel": w_out},
                  lin)


def _by_rows(fn, x):
    """fn over ``x`` (T, d) ROW_BLOCK rows at a time (a 28k-token request's
    10,944-wide activations are over a GB in float32)."""
    t = x.shape[0]
    blk = min(ROW_BLOCK, t)
    pad = -t % blk
    xs = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, blk, x.shape[1])
    return jax.lax.map(fn, xs).reshape(-1, x.shape[1])[:t]


def route(h, p, cfg: dict, mode="f32"):
    """(expert ids (T, k), weights (T, k)) in float32 whatever the rounding
    of the linear layers."""
    k = int(cfg["experts_per_token"])
    s = jax.nn.softmax(jnp.matmul(h, p["router"]["kernel"], precision=HI), -1)
    _, order = jax.lax.top_k(s, 2 * k)
    expert = order[:, k:] if mode == "wrong_expert" else order[:, :k]
    weight = float(cfg.get("router_scale", 1.0)) * jnp.take_along_axis(
        s, expert, -1)
    return expert, weight


def experts(h, p, cfg: dict, mode, held):
    expert, weight = route(h, p, cfg, mode)
    lin = _linear_mode(mode)
    blk = min(EXPERT_BLOCK, len(held))
    if len(held) % blk:
        raise ValueError(f"{len(held)} held experts are no whole number of "
                         f"blocks of {blk}")
    ids = jnp.asarray(held, jnp.int32).reshape(-1, blk)
    shape = lambda w: w.reshape((-1, blk) + w.shape[1:])

    def block(y, inp):
        w_in, w_out, e = inp
        share = (weight[:, :, None] * (expert[:, :, None] == e)).sum(1)
        for i in range(blk):
            y = y + share[:, i:i + 1] * _swiglu(
                h, w_in[i].astype(jnp.float32), w_out[i].astype(jnp.float32),
                lin)
        return y, None

    y, _ = jax.lax.scan(block, jnp.zeros_like(h),
                        (shape(p["moe_in"]), shape(p["moe_out"]), ids))
    if mode != "no_shared":
        y = y + _swiglu(h, p["shared_in"]["kernel"], p["shared_out"]["kernel"],
                        lin)
    return y


def block(x, p, dense: bool, cfg: dict, mode="f32", held=()):
    """One layer over one sequence x (T, d). The experts' matrices stay as
    they are handed (a few of them are taken to float32 at a time)."""
    big = ("moe_in", "moe_out")
    p = {k: v if k in big else jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), v) for k, v in p.items()}
    eps = float(cfg["norm_eps"])
    x = x + mla(rms_norm(x, p["ln1"]["scale"], eps), p, cfg, mode)
    h = rms_norm(x, p["ln2"]["scale"], eps)
    lin = _linear_mode(mode)
    if dense:
        w_in = jnp.concatenate([p["mlp_gate"]["kernel"],
                                p["mlp_up"]["kernel"]], 1)
        return x + _by_rows(
            lambda hb: _swiglu(hb, w_in, p["mlp_out"]["kernel"], lin), h)
    return x + experts(h, p, cfg, mode, held)


def held_experts(cfg: dict) -> tuple:
    held = cfg.get("experts_held")
    return tuple(range(int(cfg["num_experts"]))) if held is None else tuple(
        int(e) for e in held)


@functools.lru_cache(maxsize=None)
def _jitted(cfg_items, mode, held):
    cfg = dict(cfg_items)
    layer = {dense: jax.jit(functools.partial(
        block, dense=dense, cfg=cfg, mode=mode, held=held))
        for dense in (True, False)}

    def head(p, x):
        h = rms_norm(x, p["ln_f"]["scale"].astype(jnp.float32),
                     float(cfg["norm_eps"]))
        return linear(h, p["lm_head"], _linear_mode(mode))

    return layer, jax.jit(head)


def hidden(params, tokens, cfg: dict, mode="f32"):
    """The last layer's output (T, d) before the final norm, of one
    sequence, layer by layer so that one layer's f32 weights are live at a
    time."""
    layer, _ = _jitted(_cfg_items(cfg), mode or "f32", held_experts(cfg))
    tokens = jnp.asarray(tokens, jnp.int32)
    x = params["tok_embed"]["embedding"][tokens].astype(jnp.float32)
    for i in range(int(cfg["num_layers"])):
        dense = i < int(cfg.get("dense_layers", 0))
        x = layer[dense](x, params[f"block_{i}"])
    return x


def _head_fn(params, cfg, mode):
    return functools.partial(
        _jitted(_cfg_items(cfg), mode or "f32", held_experts(cfg))[1],
        {"ln_f": params["ln_f"], "lm_head": params["lm_head"]})


def logits(params, tokens, cfg: dict, mode="f32", last: int | None = None):
    """Logits (T, vocab) of one sequence in f32. ``last`` keeps only the
    final ``last`` positions."""
    x = hidden(params, tokens, cfg, mode)
    if last is not None:
        x = x[-last:]
    return _head_fn(params, cfg, mode)(x)


class Rows(_Rows):
    """One sequence's logits formed a block of rows at a time (at 102,400
    ids the whole array of a 29k-token request is 12 GB):
    ``reference_nemotron.Rows`` over this model's hidden state and head."""

    def __init__(self, params, tokens, cfg: dict, mode="f32"):
        self.x = hidden(params, tokens, cfg, mode)
        self._head = _head_fn(params, cfg, mode)
