"""The Mamba-2 mixer (Dao and Gu, "Transformers are SSMs", arXiv:2405.21060)
as a sublayer of ``models/transformer.PatternBlock``: the ``M`` layers of a
``layer_pattern`` config.

With H heads of P channels (``d_inner`` = H P), a state of N a channel, B
and C shared by G groups of H / G heads, K convolution taps, and h the
normalised input:

1. ``[z | u | dt] = h W_in``, widths d_inner | d_inner + 2 G N | H;
2. a causal depthwise convolution with bias over ``u``, then SiLU:
   ``c_t = silu(b + sum_k w[:, k] u_{t-K+1+k})``, inputs before position 0
   zero; ``c = [x (d_inner) | B (G N) | C (G N)]``, head n reads group
   ``n // (H / G)``;
3. ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` (a scalar a head);
4. the recurrence, per head, on a state S of P x N (zero before position 0):
   ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``,
   ``y_t = S_t C_t + D x_t``;
5. ``v = y * silu(z)``, RMS-normalised over each of G groups of d_inner / G
   channels, times a learned weight;
6. ``v W_out``.

What a sequence carries from token to token is the STATE: S (H, P, N) in
float32 and the last K - 1 inputs ``u`` of the convolution. Step 4 has two
forms, one implementation each:

* :func:`ssd_scan`, a whole sequence or a prefill segment: the SSD form
  over blocks of ``ssm_block`` positions. Inside a block
  ``y = (L . C B^T) (dt x)`` with L the lower-triangular products of the
  decays (matmuls); between blocks the state of the block before, decayed
  (a scan over blocks, not over positions). It takes the state in and gives
  it out; a position whose ``dt`` is 0 neither decays nor feeds the state, so
  padding behind a segment's last real token leaves the state as that token
  left it.
* :func:`ssm_step`, the decode round: step 4 as written, all lanes at once,
  elementwise on the state leaf (once in, once out). A masked lane has
  ``dt`` 0 and keeps its state bit for bit.

Decays and the state accumulate in float32 in both; the products take
``compute_dtype`` operands, as the Dense layers do.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["mamba_mixer", "ssd_scan", "ssm_step"]


def ssd_scan(x, dt, a, bm, cm, s0, block: int):
    """Step 4 over a sequence. ``x`` (B, L, H, P); ``dt`` (B, L, H) f32,
    already softplus'ed, 0 where a position is padding; ``a`` (H,) f32,
    negative; ``bm`` / ``cm`` (B, L, G, N); ``s0`` (B, H, P, N) f32, the
    state before position 0 of the sequence. Returns ``(y (B, L, H, P) f32
    without the D term, state behind the last position (B, H, P, N)
    f32)``."""
    b, l, h, p = x.shape
    g, n = bm.shape[2:]
    k = h // g  # heads a group
    q = int(block)
    pad = -l % q
    if pad:
        x, dt, bm, cm = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                         for t in (x, dt, bm, cm))
    nc = (l + pad) // q
    dt_ = x.dtype
    xs = x.reshape(b, nc, q, g, k, p)
    dts = dt.reshape(b, nc, q, g, k)
    bs, cs = bm.reshape(b, nc, q, g, n), cm.reshape(b, nc, q, g, n)
    # Inclusive sums of dt A inside a block: position i has decayed the
    # block's incoming state by exp(cum[i]).
    cum = jnp.cumsum(dts * a.reshape(g, k), axis=2)
    # Inside a block: L[i, j] = exp(cum[i] - cum[j]) for j <= i.
    tri = np.tril(np.ones((q, q), bool))[None, None, :, :, None, None]
    seg = cum[:, :, :, None] - cum[:, :, None, :]  # (b, nc, i, j, g, k)
    lmat = jnp.exp(jnp.where(tri, seg, -jnp.inf))
    scores = jnp.einsum("bcign,bcjgn->bcijg", cs, bs,
                        preferred_element_type=jnp.float32)
    w = scores[..., None] * lmat * dts[:, :, None]
    y = jnp.einsum("bcijgk,bcjgkp->bcigkp", w.astype(dt_), xs,
                   preferred_element_type=jnp.float32)
    # What each block adds to the state by its end.
    to_end = jnp.exp(cum[:, :, -1:] - cum) * dts
    states = jnp.einsum(
        "bcjgkp,bcjgn->bcgkpn",
        (xs.astype(jnp.float32) * to_end[..., None]).astype(dt_), bs,
        preferred_element_type=jnp.float32)
    block_decay = jnp.exp(cum[:, :, -1])  # (b, nc, g, k)

    def carry(s, inp):
        add, dec = inp
        return dec[..., None, None] * s + add, s  # the state BEFORE the block

    s_end, before = jax.lax.scan(
        carry, s0.reshape(b, g, k, p, n).astype(jnp.float32),
        (states.swapaxes(0, 1), block_decay.swapaxes(0, 1)))
    y = y + jnp.einsum(
        "bcign,bcgkpn->bcigkp", cs, before.swapaxes(0, 1).astype(dt_),
        preferred_element_type=jnp.float32) * jnp.exp(cum)[..., None]
    return y.reshape(b, nc * q, h, p)[:, :l], s_end.reshape(b, h, p, n)


def ssm_step(x, dt, a, bm, cm, s):
    """Step 4 for one token a lane. ``x`` (B, H, P); ``dt`` (B, H) f32, 0 in
    a masked lane; ``a`` (H,); ``bm`` / ``cm`` (B, G, N); ``s`` (B, H, P, N)
    f32. Returns ``(y (B, H, P) f32 without the D term, the new state)``."""
    b, h, p = x.shape
    g, n = bm.shape[1:]
    k = h // g
    s = s.reshape(b, g, k, p, n)
    dts = dt.reshape(b, g, k)
    dec = jnp.exp(dts * a.reshape(g, k))
    xdt = x.reshape(b, g, k, p).astype(jnp.float32) * dts[..., None]
    bf, cf = bm.astype(jnp.float32), cm.astype(jnp.float32)
    new = (dec[..., None, None] * s
           + xdt[..., None] * bf[:, :, None, None, :])
    y = (new * cf[:, :, None, None, :]).sum(-1)
    return y.reshape(b, h, p), new.reshape(b, h, p, n)


def mamba_mixer(mod, cfg, h, cache=None):
    """The mixer's output for the normalised input ``h`` (B, S, d_model) in
    ``compute_dtype``; parameters are declared on ``mod``. ``cache=None``: a
    whole sequence from position 0, zero state; returns ``y``. With a cache
    ``{'ssm' (B, H, P, N) f32, 'conv' (B, K-1, conv width), 'n_real' (B,)}``
    returns ``(y, {'ssm', 'conv'})``: the state behind each sequence's last
    real row (``n_real`` of the S fed; a masked decode lane's 0 rows leave it
    as it was). A cache with ``pages`` is the decode round (S = 1,
    :func:`ssm_step`); any other one a prefill segment (:func:`ssd_scan`)."""
    heads, p, n, g = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                      cfg.ssm_groups)
    inner, cw, taps = cfg.ssm_inner, cfg.ssm_conv_width, cfg.ssm_conv
    b, s, _ = h.shape
    f32 = jnp.float32
    with jax.named_scope("ssm.proj"):
        zud = nn.Dense(inner + cw + heads, dtype=cfg.compute_dtype,
                       use_bias=cfg.use_bias, name="in_proj")(h)
    z, u, dt = zud[..., :inner], zud[..., inner:inner + cw], zud[..., inner + cw:]
    with jax.named_scope("ssm.conv"):
        hist = (jnp.zeros((b, taps - 1, cw), u.dtype) if cache is None
                else cache["conv"].astype(u.dtype))
        full = jnp.concatenate([hist, u], 1)  # row i is position i - (K - 1)
        w = mod.param("conv_w", nn.initializers.normal(taps ** -0.5),
                      (cw, taps)).astype(f32)
        bias = mod.param("conv_b", nn.initializers.zeros, (cw,)).astype(f32)
        ff = full.astype(f32)
        c = nn.silu(bias + sum(ff[:, j:j + s] * w[:, j] for j in range(taps)))
        c = c.astype(cfg.compute_dtype)
    x = c[..., :inner].reshape(b, s, heads, p)
    bm = c[..., inner:inner + g * n].reshape(b, s, g, n)
    cm = c[..., inner + g * n:].reshape(b, s, g, n)
    a = -jnp.exp(mod.param(
        "A_log", lambda k_, sh: jnp.log(jnp.arange(1, sh[0] + 1, dtype=f32)),
        (heads,)).astype(f32))
    d_skip = mod.param("D", nn.initializers.ones, (heads,)).astype(f32)
    dt_bias = mod.param("dt_bias", nn.initializers.zeros, (heads,)).astype(f32)
    dt = jax.nn.softplus(dt.astype(f32) + dt_bias)
    if cache is not None:
        # Rows behind the last real one neither decay nor feed the state.
        real = jnp.arange(s)[None, :] < cache["n_real"][:, None]
        dt = jnp.where(real[..., None], dt, 0.0)
    if cache is not None and "pages" in cache:
        if s != 1:
            raise ValueError(f"a decode round feeds one token a slot, got {s}")
        with jax.named_scope("ssm.step"):
            y, state = ssm_step(x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0],
                                cache["ssm"])
            y = y[:, None]
    else:
        with jax.named_scope("ssm.scan"):
            s0 = (jnp.zeros((b, heads, p, n), f32) if cache is None
                  else cache["ssm"])
            y, state = ssd_scan(x, dt, a, bm, cm, s0, cfg.ssm_block)
    y = y + d_skip[:, None] * x.astype(f32)
    with jax.named_scope("ssm.proj"):
        v = y.reshape(b, s, inner) * nn.silu(z.astype(f32))
        vg = v.reshape(b, s, g, inner // g)
        vg = vg * jax.lax.rsqrt((vg * vg).mean(-1, keepdims=True) + cfg.norm_eps)
        gain = mod.param("ssm_norm", nn.initializers.ones, (inner,))
        v = (vg.reshape(b, s, inner) * gain.astype(f32)).astype(cfg.compute_dtype)
        out = nn.Dense(cfg.d_model, dtype=cfg.compute_dtype,
                       use_bias=cfg.use_bias, name="out_proj")(v)
    if cache is None:
        return out
    conv = jax.vmap(
        lambda rows, k_: jax.lax.dynamic_slice_in_dim(rows, k_, taps - 1)
    )(full, cache["n_real"]).astype(cache["conv"].dtype)
    return out, {"ssm": state, "conv": conv}
