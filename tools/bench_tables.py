"""Regenerate BASELINE.md's measured tables (VERDICT r1 #9).

Round 1 measured these by hand and recorded them as prose; this tool
re-measures them on the attached chip and emits each row as a JSON line
plus a ready-to-paste markdown table, so every table in BASELINE.md
"Measured" sections is reproducible with one command per round:

    python tools/bench_tables.py --table dispatch_modes
    python tools/bench_tables.py --table long_context
    python tools/bench_tables.py --table retrain

(The flash-kernel and LM-MFU tables are re-measured by ``bench.py`` itself
every round — this tool covers the remaining three.)

All timings use the device_get completion barrier (a host transfer of a
value that depends on every timed dispatch — bench.py module docstring).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def _emit(rows: list[dict], columns: list[str]) -> None:
    for r in rows:
        print(json.dumps(r))
    print()
    print("| " + " | ".join(columns) + " |")
    print("|" + "---|" * len(columns))
    for r in rows:
        print("| " + " | ".join(str(r[c]) for c in columns) + " |")


def table_dispatch_modes(args) -> None:
    """MNIST convnet steps/s/chip per input/dispatch mode (the BASELINE.md
    'Input/dispatch mode' table): host-batch unfused, host-batch fused,
    device pool fused x100 and x1000. Each mode runs bench.py headline in a
    subprocess so the chip is owned by exactly one JAX client at a time."""
    import subprocess

    rows = []
    for mode, k, steps in (
        ("host", 1, 200),
        ("host", 100, 2000),
        ("pool", 100, 2000),
        ("pool", 1000, 3000),
    ):
        env = dict(
            BENCH_SUITE="headline",
            BENCH_MODE=mode,
            BENCH_STEPS_PER_CALL=str(k),
            BENCH_TIMED_STEPS=str(steps),
            BENCH_WARMUP_STEPS=str(min(k, 100)),
        )
        proc = subprocess.run(
            [sys.executable, os.path.join(os.path.dirname(__file__), "..", "bench.py")],
            env={**os.environ, **env},
            capture_output=True,
            text=True,
            timeout=900,
        )
        if proc.returncode != 0:
            raise RuntimeError(proc.stderr[-1500:])
        rec = json.loads(proc.stdout.strip().splitlines()[-1])
        rows.append(
            {
                "mode": f"{mode} x{k}/dispatch",
                "steps_per_sec_per_chip": rec["value"],
            }
        )
    _emit(rows, ["mode", "steps_per_sec_per_chip"])


def table_long_context(args) -> None:
    """TransformerLM long-context envelope (BASELINE.md: d_model 256,
    **2 heads (dh=128)** since the r5 re-spec — dh=32 lane-pads BHSD
    buffers 4x in HBM and was the whole r4 "128k OOM wall"; 4 layers,
    d_ff 1024, batch 1, flash+remat) at 16k/32k/64k/128k, plus windowed
    rows at 32k/128k (window 4096, the Mistral-style config a real 128k
    model ships). A shape that exceeds the chip records an OOM row (a
    measured wall is a result; silence is not — VERDICT r3 #8).

    Harness note: this loop drains every 3 dispatches, which taxes the FAST short-context rows (~16 vs ~23 steps/s at 16k);
    the BASELINE.md envelope table quotes `tools/train_lm.py`'s drained-
    window progress lines (the hot-loop number). At 128k the two agree
    (~0.8 steps/s — step time dwarfs the drain)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from distributed_tensorflow_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )
    from distributed_tensorflow_tpu.parallel import data_parallel as dp
    from distributed_tensorflow_tpu.parallel.mesh import make_mesh
    from distributed_tensorflow_tpu.utils.compile_cache import (
        enable_compilation_cache,
    )

    enable_compilation_cache()
    mesh = make_mesh()
    rows = []
    for seq, window in (
        (16384, None), (32768, None), (65536, None), (131072, None),
        (32768, 4096), (131072, 4096),
    ):
        cfg = TransformerConfig(
            vocab_size=256, d_model=256, num_heads=2, num_layers=4, d_ff=1024,
            max_seq_len=seq, attention="flash", remat=True,
            attention_window=window,
            compute_dtype=jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32,
        )
        tx = optax.adam(1e-4)
        host = jax.device_get(
            TransformerLM(cfg).init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
            )["params"]
        )
        p = dp.replicate(host, mesh)
        o = dp.replicate(jax.device_get(tx.init(host)), mesh)
        g = dp.replicate(jnp.zeros((), jnp.int32), mesh)
        step = dp.build_lm_train_step(cfg, tx, mesh, donate=False)
        toks = dp.shard_global_batch(
            {"x": np.random.default_rng(0).integers(0, 256, (1, seq)).astype(np.int32)},
            mesh,
        )["x"]
        key = jax.random.PRNGKey(0)
        try:
            p, o, g, _m = step(p, o, g, toks, key)  # compile + warm
            base = int(jax.device_get(g))
        except Exception as e:  # HBM/VMEM wall: record it, keep the table
            import re as _re

            msg = str(e)
            m = _re.search(r"Ran out of memory[^.]*\. Used [^.]*\.", msg)
            kind = "OOM" if (m or "oom" in msg.lower()) else "ERROR"
            rows.append(
                {
                    "context": seq,
                    "window": window or "full",
                    "steps_per_sec": kind,
                    "tokens_per_sec": (m.group(0) if m else msg[:110]),
                }
            )
            del p, o, g, toks
            continue
        t0 = time.perf_counter()
        while True:  # ~args.seconds of timed steps, 3 dispatches per drain
            for _ in range(3):
                p, o, g, _m = step(p, o, g, toks, key)
            done = int(jax.device_get(g)) - base
            if time.perf_counter() - t0 >= args.seconds:
                break
        dt = (time.perf_counter() - t0) / done
        rows.append(
            {
                "context": seq,
                "window": window or "full",
                "steps_per_sec": round(1.0 / dt, 2),
                "tokens_per_sec": round(seq / dt, 0),
            }
        )
        del p, o, g, toks  # free HBM before the next (larger) context
    _emit(rows, ["context", "window", "steps_per_sec", "tokens_per_sec"])


def table_retrain(args) -> None:
    """retrain1 end-to-end wall-clock on the bundled sample_images, 100 head
    steps (the BASELINE.md retrain table). Two runs in one temp dir: the
    first pays bottleneck caching (cold), the second reuses it (warm); the
    XLA compile cache is whatever this machine already has, as in r1."""
    import subprocess
    import tempfile

    repo = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    rows = []
    with tempfile.TemporaryDirectory() as tmp:
        for run in ("cold-bottlenecks", "warm-bottlenecks"):
            t0 = time.perf_counter()
            proc = subprocess.run(
                [
                    sys.executable,
                    os.path.join(repo, "retrain1", "retrain.py"),
                    "--training_steps", "100",
                    "--bottleneck_dir", os.path.join(tmp, "bn"),
                    "--summaries_dir", os.path.join(tmp, "sum"),
                    "--output_graph", os.path.join(tmp, "g.msgpack"),
                    "--output_labels", os.path.join(tmp, "l.txt"),
                ],
                capture_output=True,
                text=True,
                timeout=900,
                cwd=tmp,
            )
            if proc.returncode != 0:
                raise RuntimeError(proc.stderr[-1500:])
            rows.append(
                {
                    "configuration": run,
                    "total_wall_clock_s": round(time.perf_counter() - t0, 1),
                }
            )
    _emit(rows, ["configuration", "total_wall_clock_s"])


def table_step_budget(args) -> None:
    """Per-component time budget of the flagship LM training step (VERDICT
    r2 #3): each component of the 403M-param step (bench.py LM_SHAPE) is
    timed IN ISOLATION at the step's exact shapes with the fixed-cost-
    cancelling difference method — the component body runs inside a chained
    ``lax.scan`` at two lengths and ``(t_long - t_short)/(n_long - n_short)``
    cancels the dispatch and drain round-trip exactly (BASELINE.md r3
    methodology). Each iteration's input is derived from the previous
    iteration's OUTPUTS (including a scalar folded in from every parameter
    gradient leaf), so no part of the fwd+bwd can be hoisted or DCE'd.

    The table reports ms/step (x num_layers for per-layer components), the
    component's model FLOPs share, its achieved %% of bf16 peak, and %% of the
    measured full step; components + optimizer should sum to ~the full step,
    with the residual = fusion interactions / misc the isolation can't see.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import flax.linen as nn

    from distributed_tensorflow_tpu.models import transformer as T
    from distributed_tensorflow_tpu.ops import attention as A
    from distributed_tensorflow_tpu.parallel import data_parallel as dp
    from distributed_tensorflow_tpu.parallel.mesh import make_mesh
    from distributed_tensorflow_tpu.utils.compile_cache import (
        enable_compilation_cache,
    )
    from distributed_tensorflow_tpu.utils.flops import chip_peak_flops

    if jax.default_backend() != "tpu":
        raise SystemExit("step_budget isolates Mosaic kernels; TPU required")
    enable_compilation_cache()

    import bench  # repo root (sys.path has it): the flagship shape lives there

    sh = bench.LM_SHAPE
    B, S, d, H, L, dff = (
        sh["batch"], sh["seq"], sh["d_model"], sh["num_heads"],
        sh["num_layers"], sh["d_ff"],
    )
    vocab = 256
    # EXACTLY the bench flagship definition (bench_lm_mfu): packed-qkv
    # layout-native flash ("flash" resolves to it) and bias-free Dense
    # layers — a budget measured on a different variant misattributes.
    cfg = T.TransformerConfig(
        vocab_size=vocab, d_model=d, num_heads=H, num_layers=L, d_ff=dff,
        max_seq_len=S, attention="flash", compute_dtype=jnp.bfloat16,
        use_bias=False,
    )
    if len(jax.devices()) != 1:
        # Components are timed un-sharded on one device; comparing them
        # against a mesh-wide full step would misattribute by the chip count.
        raise SystemExit("step_budget assumes a single-chip host")
    peak = chip_peak_flops()
    if peak is None:
        raise SystemExit("unknown TPU device_kind — no peak-FLOPs denominator")
    drain = lambda x: jax.device_get(x)

    def timed_pair(fn, n_long, n_short, reps=6):
        """bench._per_iter_time (per-length minima, then difference — robust
        to drain-round-trip spikes) over a chained-scan runner;
        returns None when the difference doesn't credibly scale, and the row
        is then reported as unmeasured rather than a fabricated number."""
        for n in (n_long, n_short):
            drain(fn(n))  # compile + complete

        def run(n):
            t0 = time.perf_counter()
            drain(fn(n))
            return time.perf_counter() - t0

        return bench._per_iter_time(run, n_long, n_short, reps=reps)

    def scan_component(body, x0, n_long=16, n_short=2):
        """Time one iteration of ``body`` (x -> x, same shape/dtype) via a
        chained scan at two lengths."""
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
        """x -> x body running module fwd+bwd: grads w.r.t. (params, x) are
        both computed; every param-grad leaf is folded into the carry via a
        cheap reduction so none of the backward pass can be DCE'd."""

        def body(x):
            def loss(p, xx):
                return loss_of_out(module.apply({"params": p}, xx))

            (gp, gx) = jax.grad(loss, argnums=(0, 1))(params, x)
            gp_scalar = sum(
                jnp.sum(l.astype(jnp.float32)) for l in jax.tree_util.tree_leaves(gp)
            )
            return x + 1e-3 * gx + (1e-6 * gp_scalar).astype(x.dtype)

        return body

    # Activations/tokens are generated ON DEVICE: a (B, S, d) bf16 host
    # upload is ~100 MB that the timed loop does not need to wait for.
    key = jax.random.PRNGKey(0)
    x0 = jax.jit(
        lambda k: 0.02 * jax.random.normal(k, (B, S, d), jnp.bfloat16)
    )(key)
    mean_loss = lambda out: jnp.mean(out.astype(jnp.float32) ** 2)

    class AttnSublayer(nn.Module):
        @nn.compact
        def __call__(self, x):
            return T.attention_sublayer(cfg, x, T._attention_fn(cfg, prefer_packed=True))[0]

    class FfnSublayer(nn.Module):
        @nn.compact
        def __call__(self, x):
            h = nn.LayerNorm(dtype=cfg.compute_dtype, name="ln2")(x)
            h = nn.Dense(dff, dtype=cfg.compute_dtype, name="mlp_in")(h)
            h = nn.gelu(h)
            h = nn.Dense(d, dtype=cfg.compute_dtype, name="mlp_out")(h)
            return x + h

    class Head(nn.Module):
        """Final LN + vocab head + next-token loss, plus the token/pos
        embedding lookups (their bwd is the scatter-add) — everything in the
        step outside the L blocks and the optimizer."""

        @nn.compact
        def __call__(self, h, tokens):
            e = nn.Embed(vocab, d, dtype=cfg.compute_dtype, name="tok_embed")(tokens)
            pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), tokens.shape)
            e = e + nn.Embed(S, d, dtype=cfg.compute_dtype, name="pos_embed")(pos)
            x = nn.LayerNorm(dtype=cfg.compute_dtype, name="ln_f")(e + h)
            logits = nn.Dense(vocab, dtype=cfg.compute_dtype, name="lm_head")(x)
            return T.next_token_loss(logits.astype(jnp.float32), tokens)

    tokens = jax.jit(
        lambda k: jax.random.randint(k, (B, S), 0, vocab, jnp.int32)
    )(key)

    # FLOPs accounting per component (fwd; train = 3x), matching utils/flops.
    tok = B * S
    fl_attn = 3 * (2 * tok * 4 * d * d + 4 * B * S * S * d // 2)
    fl_ffn = 3 * (2 * tok * 2 * d * dff)
    fl_head = 3 * (2 * tok * d * vocab)
    fl_flash = 3 * (4 * B * S * S * d // 2)

    rows = []

    def add(component, ms, mult=1, flops=0):
        print(f"# measured: {component}", file=sys.stderr, flush=True)
        if ms is None:  # timing discarded as non-scaling (jitter > signal)
            rows.append(
                {
                    "component": component,
                    "ms_per_step": "unmeasured",
                    "x": mult,
                    "model_tflops": round(flops * mult / 1e12, 2),
                    "pct_of_peak": "—",
                }
            )
            return
        rows.append(
            {
                "component": component,
                "ms_per_step": round(ms * mult * 1e3, 1),
                "x": mult,
                "model_tflops": round(flops * mult / 1e12, 2),
                "pct_of_peak": (
                    round(flops * mult / (ms * mult) / peak * 100, 1) if flops else "—"
                ),
            }
        )

    # --- full step, measured exactly as bench_lm_mfu does ---
    log = lambda msg: print(f"# {msg}", file=sys.stderr, flush=True)
    tx = optax.adam(1e-4)
    mesh = make_mesh()
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    model = T.TransformerLM(cfg)
    p_full = jax.jit(
        lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32))["params"],
        out_shardings=rep,
    )(key)
    o_full = jax.jit(tx.init, out_shardings=rep)(p_full)
    g = dp.replicate(jnp.zeros((), jnp.int32), mesh)
    step = dp.build_lm_train_step(cfg, tx, mesh, donate=True)
    toks_sharded = dp.shard_global_batch({"x": np.asarray(tokens)}, mesh)["x"]
    log("full step: warmup/compile")
    for _ in range(3):
        p_full, o_full, g, _m = step(p_full, o_full, g, toks_sharded, key)
    drain(g)

    def timed_window(run_step, counter, n=10, windows=3):
        """min over several n-step drained windows — the same spike defense
        the difference-method components use (one drain spike would
        otherwise inflate step_ms and skew every pct_of_step row).
        ``counter`` returns the CURRENT on-device step counter (re-read each
        window: the loop rebinds it)."""
        best = None
        for _ in range(windows):
            base = int(drain(counter()))
            t0 = time.perf_counter()
            for _ in range(n):
                run_step()
            done = int(drain(counter())) - base  # drain precedes clock read
            dt = (time.perf_counter() - t0) / done
            best = dt if best is None else min(best, dt)
        return best

    log("full step: timing")

    def _adam_step():
        nonlocal p_full, o_full, g
        p_full, o_full, g, _m = step(p_full, o_full, g, toks_sharded, key)

    step_ms = timed_window(_adam_step, lambda: g)
    # Free the full state before the component measurements need HBM.
    fl_step = (fl_attn + fl_ffn) * L + fl_head

    # --- optimizer: measured as a TX-SWAP DELTA. Directly timing an
    # isolated 403M-tree update proved unmeasurable on this runtime (a scan
    # draining one leaf is DCE'd to ~0; a scan consuming every leaf, and a
    # donated standalone-update jit, both wedge the compiler for 10+ min).
    # Instead the SAME well-behaved step builder runs with SGD in place of
    # Adam: the difference is Adam's extra work — the f32 m/v state's
    # 3.2 GB x2 HBM traffic plus its elementwise math. (The param+grad
    # read/write pass SGD itself does is fused into the backward and is not
    # separable; the sum row therefore slightly UNDER-attributes.)
    log("sgd-step: warmup/compile")
    del p_full, o_full
    tx_sgd = optax.sgd(1e-4)
    p2 = jax.jit(
        lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32))["params"],
        out_shardings=rep,
    )(key)
    o2 = jax.jit(tx_sgd.init, out_shardings=rep)(p2)
    g2 = dp.replicate(jnp.zeros((), jnp.int32), mesh)
    sgd_step = dp.build_lm_train_step(cfg, tx_sgd, mesh, donate=True)
    for _ in range(3):
        p2, o2, g2, _m = sgd_step(p2, o2, g2, toks_sharded, key)
    drain(g2)
    log("sgd-step: timing")

    def _sgd_step():
        nonlocal p2, o2, g2
        p2, o2, g2, _m = sgd_step(p2, o2, g2, toks_sharded, key)

    sgd_step_ms = timed_window(_sgd_step, lambda: g2)
    del p2, o2, g2
    adam_s = step_ms - sgd_step_ms
    if adam_s <= 0:  # a drain spike in one 10-step window — not credible
        adam_s = None
    add("adam m/v state (adam step − sgd step)", adam_s, 1, 0)

    # --- per-layer components ---
    attn_mod = AttnSublayer()
    pa = jax.jit(lambda k: attn_mod.init(k, x0)["params"], out_shardings=rep)(key)
    attn_s = scan_component(grad_chain(attn_mod, pa, mean_loss), x0)
    fwd_attn_s = scan_component(
        lambda x: x + 1e-3 * attn_mod.apply({"params": pa}, x), x0
    )
    del pa
    add("attn sublayer fwd (ln1+qkv+flash+proj)", fwd_attn_s, L, fl_attn // 3)
    add("attn sublayer fwd+bwd", attn_s, L, fl_attn)

    ffn_mod = FfnSublayer()
    pf = jax.jit(lambda k: ffn_mod.init(k, x0)["params"], out_shardings=rep)(key)
    ffn_s = scan_component(grad_chain(ffn_mod, pf, mean_loss), x0)
    fwd_ffn_s = scan_component(
        lambda x: x + 1e-3 * ffn_mod.apply({"params": pf}, x), x0
    )
    del pf
    add("ffn sublayer fwd (ln2+mlp+gelu)", fwd_ffn_s, L, fl_ffn // 3)
    add("ffn sublayer fwd+bwd", ffn_s, L, fl_ffn)

    # --- embeddings + final LN + head + loss ---
    head_mod = Head()
    ph = jax.jit(lambda k: head_mod.init(k, x0, tokens)["params"], out_shardings=rep)(
        key
    )

    def head_body(h):
        def loss(p, hh):
            return head_mod.apply({"params": p}, hh, tokens)

        gp, gh = jax.grad(loss, argnums=(0, 1))(ph, h)
        gp_scalar = sum(
            jnp.sum(l.astype(jnp.float32)) for l in jax.tree_util.tree_leaves(gp)
        )
        return h + gh.astype(h.dtype) + (1e-6 * gp_scalar).astype(h.dtype)

    head_s = scan_component(head_body, x0)
    del ph
    add("embed + final LN + head + CE loss fwd+bwd", head_s, 1, fl_head)

    # --- flash kernel alone at the step's attention shape ---
    q0 = jax.jit(
        lambda k: 0.1 * jax.random.normal(k, (B, H, S, d // H), jnp.bfloat16)
    )(key)

    def flash_body(q):
        # q, k and v all flow from the carry so the backward computes the
        # full dq + dk + dv (a constant k/v would let XLA drop the dkv
        # kernel as dead code).
        def loss(qq):
            return jnp.mean(
                A.flash_attention(
                    qq, qq, qq, causal=True, block_q=1024, block_kv=1024
                ).astype(jnp.float32)
                ** 2
            )

        return q + 1e-3 * jax.grad(loss)(q)

    flash_s = scan_component(flash_body, q0)
    add("  (flash kernel only, fwd+bwd, B*H=%d)" % (B * H), flash_s, L, fl_flash)

    # --- totals (only when every summed component actually measured) ---
    parts = [attn_s, ffn_s, head_s, adam_s]
    if all(x is not None for x in parts):
        attributed = (attn_s + ffn_s) * L + head_s + adam_s
        add("SUM of components + adam", attributed, 1, 0)
        add("FULL STEP (measured, one XLA program)", step_ms, 1, fl_step)
        add("unattributed (fusion interactions / misc)", step_ms - attributed, 1, 0)
    else:
        add("FULL STEP (measured, one XLA program)", step_ms, 1, fl_step)
    for r in rows:
        r["pct_of_step"] = (
            round(r["ms_per_step"] / (step_ms * 1e3) * 100, 1)
            if isinstance(r["ms_per_step"], (int, float)) and r["ms_per_step"]
            else "—"
        )
    _emit(rows, ["component", "ms_per_step", "x", "model_tflops", "pct_of_peak", "pct_of_step"])


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument(
        "--table",
        required=True,
        choices=("dispatch_modes", "long_context", "retrain", "step_budget"),
    )
    parser.add_argument(
        "--seconds", type=float, default=10.0,
        help="approximate timing budget per long-context row",
    )
    args = parser.parse_args(argv)
    {
        "dispatch_modes": table_dispatch_modes,
        "long_context": table_long_context,
        "retrain": table_retrain,
        "step_budget": table_step_budget,
    }[args.table](args)


if __name__ == "__main__":
    main()
