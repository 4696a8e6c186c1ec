"""Decompose the flagship attention sublayer's non-kernel time (VERDICT r3 #2).

BASELINE.md's step budget leaves ~54 ms/step inside the attention sublayer
unattributed: attn fwd+bwd 209.5 ms, flash kernel 45.8 ms, and qkv+proj at
the FFN's 91.6%-of-peak would be ~110 ms. This probe times each candidate in
ISOLATION at the step's exact shapes (B=12, S=2048, d=2048, H=16, dh=128)
with the repo's fixed-cost-cancelling chained-scan method, so the missing
milliseconds get an owner before any fix is attempted.

Run: python tools/attn_probe.py   (TPU required)
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import flax.linen as nn
import jax
import jax.numpy as jnp

import bench
from distributed_tensorflow_tpu.models import transformer as T
from distributed_tensorflow_tpu.ops import attention as A
from distributed_tensorflow_tpu.utils.compile_cache import enable_compilation_cache
from distributed_tensorflow_tpu.utils.flops import chip_peak_flops

enable_compilation_cache()

sh = bench.LM_SHAPE
B, S, d, H, L, dff = (
    sh["batch"], sh["seq"], sh["d_model"], sh["num_heads"], sh["num_layers"], sh["d_ff"],
)
dh = d // H
peak = chip_peak_flops()
key = jax.random.PRNGKey(0)
drain = lambda x: jax.device_get(x)

cfg = T.TransformerConfig(
    vocab_size=256, d_model=d, num_heads=H, num_layers=L, d_ff=dff, max_seq_len=S,
    attention="flash",  # resolves to the BSHD-native kernel path
    compute_dtype=jnp.bfloat16,
)
cfg_bhsd = T.TransformerConfig(
    vocab_size=256, d_model=d, num_heads=H, num_layers=L, d_ff=dff, max_seq_len=S,
    attention=lambda q, k, v: A.flash_attention(q, k, v, causal=True, block_q=1024, block_kv=1024),
    compute_dtype=jnp.bfloat16,
)

x0 = jax.jit(lambda k: 0.02 * jax.random.normal(k, (B, S, d), jnp.bfloat16))(key)
mean_loss = lambda out: jnp.mean(out.astype(jnp.float32) ** 2)


def timed_pair(fn, n_long, n_short, reps=6):
    for n in (n_long, n_short):
        drain(fn(n))

    def run(n):
        t0 = time.perf_counter()
        drain(fn(n))
        return time.perf_counter() - t0

    return bench._per_iter_time(run, n_long, n_short, reps=reps)


def scan_with_input(body, x0, n_long=16, n_short=2):
    fns = {}

    def make(n):
        @jax.jit
        def run(x):
            out = jax.lax.scan(lambda c, _: (body(c), None), x, None, length=n)[0]
            return jnp.sum(out.astype(jnp.float32))

        return run

    def fn(n):
        if n not in fns:
            fns[n] = make(n)
        return fns[n](x0)

    return timed_pair(fn, n_long, n_short)


def grad_chain(module, params, loss_of_out):
    def body(x):
        def loss(p, xx):
            return loss_of_out(module.apply({"params": p}, xx))

        gp, gx = jax.grad(loss, argnums=(0, 1))(params, x)
        gp_scalar = sum(
            jnp.sum(l.astype(jnp.float32)) for l in jax.tree_util.tree_leaves(gp)
        )
        return x + 1e-3 * gx + (1e-6 * gp_scalar).astype(x.dtype)

    return body


def report(name, ms, flops=0):
    if ms is None:
        print(f"{name:55s}  UNMEASURED", flush=True)
        return
    pct = f"  {flops / ms / peak * 100:5.1f}% peak" if flops else ""
    print(f"{name:55s}  {ms*1e3*L:7.1f} ms/step ({ms*1e3:6.2f} ms/layer){pct}", flush=True)


def module_probe(mod_cls, name, flops=0, x=None):
    mod = mod_cls()
    x = x0 if x is None else x
    p = jax.jit(lambda k: mod.init(k, x)["params"])(key)
    ms = scan_with_input(grad_chain(mod, p, mean_loss), x)
    report(name, ms, flops)
    return ms


tok = B * S
fl_qkv = 3 * 2 * tok * 3 * d * d   # fwd+bwd(2x) of x@W_qkv
fl_proj = 3 * 2 * tok * d * d
fl_flash = 3 * (4 * B * S * S * d // 2)
fl_attn = 3 * (2 * tok * 4 * d * d) + fl_flash


class AttnSublayer(nn.Module):
    @nn.compact
    def __call__(self, x):
        return T.attention_sublayer(cfg, x, T._attention_fn(cfg, prefer_packed=True))[0]


class AttnSublayerBhsd(nn.Module):
    @nn.compact
    def __call__(self, x):
        return T.attention_sublayer(cfg_bhsd, x, T._attention_fn(cfg_bhsd))[0]


class AttnNoFlash(nn.Module):
    """Everything but the kernel: attend = identity on v (grads flow to q,k
    through a cheap sum so qkv's backward still runs in full)."""

    @nn.compact
    def __call__(self, x):
        attend = lambda q, k, v: v + (q.sum() * 1e-9 + k.sum() * 1e-9).astype(v.dtype)
        return T.attention_sublayer(cfg, x, attend)[0]


class Ln1(nn.Module):
    @nn.compact
    def __call__(self, x):
        return nn.LayerNorm(dtype=cfg.compute_dtype)(x)


class QkvDense(nn.Module):
    @nn.compact
    def __call__(self, x):
        y = nn.Dense(3 * d, dtype=cfg.compute_dtype)(x)
        # reduce back to carry shape with a cheap slice so the carry stays (B,S,d)
        return y[..., :d] + y[..., d : 2 * d] * 1e-3 + y[..., 2 * d :] * 1e-3


class ProjDense(nn.Module):
    @nn.compact
    def __call__(self, x):
        return nn.Dense(d, dtype=cfg.compute_dtype)(x)


class PackOnly(nn.Module):
    """The transposes alone: split -> (B,H,S,dh) -> merge of q+k+v -> back."""

    @nn.compact
    def __call__(self, x):
        w = self.param("w", nn.initializers.ones, (3,), jnp.bfloat16)
        q = x * w[0]
        k = x * w[1]
        v = x * w[2]
        to_heads = lambda t: t.reshape(B, S, H, dh).transpose(0, 2, 1, 3)
        attn = to_heads(q) + to_heads(k) * 1e-3 + to_heads(v) * 1e-3
        return attn.transpose(0, 2, 1, 3).reshape(B, S, d)


class EinsumHeads(nn.Module):
    """Candidate fix shape: per-head einsum straight to (B,H,S,dh)."""

    @nn.compact
    def __call__(self, x):
        wq = self.param("wq", nn.initializers.normal(0.02), (d, H, dh), jnp.float32)
        q = jnp.einsum("bsd,dhe->bhse", x, wq.astype(x.dtype))
        return jnp.einsum("bhse,dhe->bsd", q, wq.astype(x.dtype))


def flash_probe():
    q0 = jax.jit(
        lambda k: 0.1 * jax.random.normal(k, (B, H, S, dh), jnp.bfloat16)
    )(key)

    def body(q):
        def loss(qq):
            return jnp.mean(
                A.flash_attention(qq, qq, qq, causal=True, block_q=1024, block_kv=1024)
                .astype(jnp.float32) ** 2
            )

        return q + 1e-3 * jax.grad(loss)(q)

    ms = scan_with_input(body, q0)
    report("flash kernel only fwd+bwd", ms, fl_flash)
    return ms


def main():
    if jax.default_backend() != "tpu":
        raise SystemExit("TPU required")
    print(f"flagship shapes: B={B} S={S} d={d} H={H} dh={dh}  ({L} layers/step)")
    full = module_probe(AttnSublayer, "attn sublayer fwd+bwd (packed-qkv native)", fl_attn)
    module_probe(AttnSublayerBhsd, "attn sublayer fwd+bwd (BHSD transposes)", fl_attn)
    noflash = module_probe(AttnNoFlash, "attn sublayer minus flash (identity attend)",
                           fl_attn - fl_flash)
    flash = flash_probe()
    # Per-component candidates (unreliable on noisy days — each may
    # report UNMEASURED; the XPlane trace is the authoritative attribution,
    # BASELINE.md r4 section). QkvDense/EinsumHeads carry a caveat: XLA can
    # algebraically fold their slice-sum / double-einsum reductions, so
    # their % figures are lower bounds on the real matmul cost.
    module_probe(Ln1, "ln1 alone fwd+bwd")
    module_probe(QkvDense, "qkv Dense alone fwd+bwd (foldable, see note)", fl_qkv)
    module_probe(ProjDense, "proj Dense alone fwd+bwd", fl_proj)
    module_probe(PackOnly, "head split+transpose+untranspose alone fwd+bwd")
    module_probe(EinsumHeads, "einsum-to-heads q+out pair (foldable, see note)")
    if full and noflash and flash:
        print(f"\nfull - noflash = {(full - noflash)*1e3*L:.1f} ms/step "
              f"(flash kernel measured alone: {flash*1e3*L:.1f})")


if __name__ == "__main__":
    main()
