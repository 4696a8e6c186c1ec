"""The plain reference of the Nemotron-H stage (NVIDIA-Nemotron-3-Nano): the
forward pass in straightforward ``jax.numpy`` and float32, matrix products at
``highest`` precision. It imports nothing of the program, holds no cache, no
pages, no state leaf and no kernel, and takes one sequence at a time.

``x_0 = E[token]`` (no position signal of any kind). Layer i of kind
``layer_pattern[i]`` is ``x <- x + mixer(RMSNorm(x))`` (epsilon
``norm_eps``, a learned weight); then a final RMSNorm and ``logits = h
W_head`` (its own matrix). The configuration file lists what of this is
``assumed``.

``M``, the Mamba-2 mixer, as the RECURRENCE (a ``lax.scan`` over positions;
the program's prefill runs the SSD block form, which is thereby checked
against an independent formulation): ``[z | u | dt] = h W_in``; ``c_t =
silu(b + sum_k w[:, k] u_{t-K+1+k})`` (zeros before position 0), ``c = [x |
B | C]``; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head n of
group ``n // (H / G)``: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``,
``y_t = S_t C_t + D x_t``; ``v = y * silu(z)`` RMS-normalised over each of G
groups of channels, times a learned weight; ``v W_out``.

``E``, the expert layer: ``s = sigmoid(h W_r)``; the k largest of ``s + b``;
weights ``scale * s[e] / (sum of the k + 1e-20)``; ``sum_e w[e] W_down^e
relu(h W_up^e)^2`` over the experts held, by a mask over experts taken
``EXPERT_BLOCK`` at a time (a layer's experts are 5.2 GB in float32), plus
the shared expert ``W_down^s relu(h W_up^s)^2`` on every token, unweighted.
The router is float32 in every mode.

``*``: ``q, k, v = h W_qkv``; causal ``softmax(q k^T / sqrt(dh)) v`` with
grouped kv heads, no rotation; ``o W_o``.

``mode`` rounds the linear layers (``in_proj``, ``out_proj``, ``qkv``,
``proj``, the experts, the shared expert, the head) as
``benchmarks/reference.py`` does. Four more modes are float32 with a fault
(``FAULTS``), the controls that say what the cell's comparison can see:
``"wrong_expert"`` sends every token's k pairs to the experts ranked k+1 to
2k; ``"state_bf16"`` rounds the recurrent state to bfloat16 after every
position (where the configuration states float32); ``"no_shared"`` drops the
shared expert; ``"router_bf16"`` rounds the router's input and matrix to
bfloat16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference import HI, _cfg_items, linear
from benchmarks.reference import gap_rows as _gap_rows
from benchmarks.reference import margin_rows as _margin_rows
from benchmarks.weights_nemotron import gated, held_experts

FAULTS = ("wrong_expert", "state_bf16", "no_shared", "router_bf16")
EXPERT_BLOCK = 16


def _linear_mode(mode):
    """The rounding of the linear layers under ``mode``: none under a
    fault."""
    return "f32" if mode in FAULTS else mode


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def shift(x, j):
    """x (T, ...) delayed by j positions, zeros before position 0."""
    return x if j == 0 else jnp.pad(x, ((j, 0),) + ((0, 0),) * (x.ndim - 1))[
        : x.shape[0]]


def mamba(h, p, cfg, mode):
    t = h.shape[0]
    heads, hp, n, g, taps = (int(cfg[k]) for k in (
        "ssm_heads", "ssm_head_dim", "ssm_state", "ssm_groups", "ssm_conv"))
    inner = heads * hp
    lin = _linear_mode(mode)
    zud = linear(h, p["in_proj"], lin)
    z, u, dt = jnp.split(zud, [inner, zud.shape[1] - heads], axis=1)
    c = jax.nn.silu(p["conv_b"] + sum(
        shift(u, taps - 1 - k) * p["conv_w"][:, k] for k in range(taps)))
    x = c[:, :inner].reshape(t, heads, hp)
    bm = jnp.repeat(c[:, inner:inner + g * n].reshape(t, g, n),
                    heads // g, axis=1)
    cm = jnp.repeat(c[:, inner + g * n:].reshape(t, g, n), heads // g, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["A_log"])

    def step(s, inp):
        x_t, b_t, c_t, dt_t = inp
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        if mode == "state_bf16":
            s = s.astype(jnp.bfloat16).astype(jnp.float32)
        return s, (s * c_t[:, None, :]).sum(-1)

    _, y = jax.lax.scan(step, jnp.zeros((heads, hp, n), jnp.float32),
                        (x, bm, cm, dt))
    y = y + p["D"][:, None] * x
    v = (y.reshape(t, inner) * jax.nn.silu(z)).reshape(t, g, inner // g)
    v = rms_norm(v, 1.0, float(cfg["norm_eps"])).reshape(t, inner)
    return linear(v * p["ssm_norm"], p["out_proj"], lin)


def route(h, p, cfg, mode="f32"):
    """(expert ids (T, k), their weights (T, k)) in float32 whatever the
    rounding of the linear layers."""
    k = int(cfg["experts_per_token"])
    w = p["router"]["kernel"]
    if mode == "router_bf16":
        h, w = (a.astype(jnp.bfloat16).astype(jnp.float32) for a in (h, w))
    s = jax.nn.sigmoid(jnp.matmul(h, w, precision=HI))
    _, order = jax.lax.top_k(s + p["router_bias"], 2 * k)
    expert = order[:, k:] if mode == "wrong_expert" else order[:, :k]
    picked = jnp.take_along_axis(s, expert, -1)
    weight = float(cfg.get("router_scale", 1.0)) * picked / (
        picked.sum(-1, keepdims=True) + 1e-20)
    return expert, weight


def act(u, cfg):
    if gated(cfg):
        wide = u.shape[-1] // 2
        return jax.nn.silu(u[..., :wide]) * u[..., wide:]
    return jnp.square(jax.nn.relu(u))


def experts(h, p, cfg, mode, held):
    """The held experts' part of the layer's output, and the shared
    expert's (every share computes it)."""
    expert, weight = route(h, p, cfg, mode)
    lin = _linear_mode(mode)
    blk = min(EXPERT_BLOCK, len(held))
    if len(held) % blk:
        raise ValueError(f"{len(held)} held experts are no whole number of "
                         f"blocks of {blk}")
    ids = jnp.asarray(held, jnp.int32).reshape(-1, blk)
    shape = lambda w: w.reshape((-1, blk) + w.shape[1:])

    # Into an expert: ``moe_in`` (d, 2 width: gate | up) as it lies, or
    # ``moe_up`` (width, d), a checkpoint's (out, in), transposed below.
    w_first = p["moe_in"] if gated(cfg) else p["moe_up"]

    def block(y, inp):
        w_in, w_out, e = inp
        # (T,) weight a token gives each expert of the block: 0 unless chosen.
        share = (weight[:, :, None] * (expert[:, :, None] == e)).sum(1)
        for i in range(blk):
            first = w_in[i] if gated(cfg) else w_in[i].T
            mid = act(linear(h, {"kernel": first}, lin), cfg)
            y = y + share[:, i:i + 1] * linear(mid, {"kernel": w_out[i]}, lin)
        return y, None

    y, _ = jax.lax.scan(block, jnp.zeros_like(h),
                        (shape(w_first), shape(p["moe_out"]), ids))
    if "shared_in" in p and mode != "no_shared":
        y = y + linear(act(linear(h, p["shared_in"], lin), cfg),
                       p["shared_out"], lin)
    return y


def attention(h, p, cfg, mode):
    t = h.shape[0]
    heads, kv, dh = (int(cfg[k]) for k in (
        "num_heads", "num_kv_heads", "head_dim"))
    lin = _linear_mode(mode)
    q, k, v = jnp.split(linear(h, p["qkv"], lin),
                        [heads * dh, (heads + kv) * dh], axis=1)
    scores = jnp.einsum("tkgd,skd->kgts", q.reshape(t, kv, heads // kv, dh),
                        k.reshape(t, kv, dh), precision=HI) / dh ** 0.5
    pos = jnp.arange(t)
    scores = jnp.where((pos[None, :] <= pos[:, None])[None, None], scores,
                       -1e30)
    out = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(scores, -1),
                     v.reshape(t, kv, dh), precision=HI)
    return linear(out.reshape(t, heads * dh), p["proj"], lin)


def block(x, p, kind: str, cfg: dict, mode="f32", held=()):
    """One layer over one sequence x (T, d). The experts' matrices stay as
    they are handed (a block of them is taken to float32 at a time)."""
    big = ("moe_in", "moe_up", "moe_out")
    p = {k: v if k in big else jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), v) for k, v in p.items()}
    h = rms_norm(x, p["ln1"]["scale"], float(cfg["norm_eps"]))
    if kind == "M":
        return x + mamba(h, p, cfg, mode)
    if kind == "E":
        return x + experts(h, p, cfg, mode, held)
    return x + attention(h, p, cfg, mode)


def head(params, x, cfg: dict, mode="f32"):
    h = rms_norm(x, params["ln_f"]["scale"].astype(jnp.float32),
                 float(cfg["norm_eps"]))
    return linear(h, params["lm_head"], _linear_mode(mode))


@functools.lru_cache(maxsize=None)
def _jitted(cfg_items, mode, held):
    cfg = dict(cfg_items)
    layer = {kind: jax.jit(functools.partial(
        block, kind=kind, cfg=cfg, mode=mode, held=held)) for kind in "ME*"}
    return layer, jax.jit(lambda p, x: head(p, x, cfg, mode))


def hidden(params, tokens, cfg: dict, mode="f32"):
    """The last layer's output (T, d) before the final norm, of one
    sequence, layer by layer so that one layer's f32 weights are live at a
    time."""
    layer, _ = _jitted(_cfg_items(cfg), mode or "f32", held_experts(cfg))
    tokens = jnp.asarray(tokens, jnp.int32)
    x = params["tok_embed"]["embedding"][tokens].astype(jnp.float32)
    for i, kind in enumerate(cfg["layer_pattern"]):
        x = layer[kind](x, params[f"block_{i}"])
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


class Rows:
    """One sequence's logits, formed ``BLOCK`` rows at a time (at 131,072
    ids the whole array of a 3,584-token request is 1.9 GB): what the runner
    asks of logits is here, :func:`gap_rows`, :func:`margin_rows` and
    ``argmax``. As ``reference_zaya.Rows``."""

    BLOCK = 512

    def __init__(self, params, tokens, cfg: dict, mode="f32"):
        self.x = hidden(params, tokens, cfg, mode)
        self._head = _head_fn(params, cfg, mode)

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
