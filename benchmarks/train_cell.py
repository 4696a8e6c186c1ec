"""Runner of the training cells: the step ``build_lm_train_step`` returns, on
a data-parallel mesh as ``tools/train_lm.py``'s ``dp`` mode builds it
(forward of ``models/transformer.py``, the flash kernels of
``ops/attention.py``, ``next_token_loss``, the optax update, and across
chips the ``pmean``).

Set-up builds ONE object, the compiled step with its state, drives it from
the seed through its first three steps by the window's own call and feed,
and hands that same object to the window. The reference follows those three
steps after the window has closed and the program's state is freed.
"""

from __future__ import annotations

import gc
import statistics
import time

from benchmarks import common, reference, traffic, weights

CHECK_STEPS = 3
B1, B2, ADAM_EPS = 0.9, 0.999, 1e-8


def _leaf_names(tree):
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [".".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in flat]


def split_fused(tree, mcfg):
    """The fused q|k|v projection as the three leaves the published block
    has. A key's bias has no gradient under softmax, so inside the fused
    leaf a third of the bias would move under Adam by round-off alone and
    hide from the rule that leaves such leaves out."""
    d = int(mcfg["d_model"])
    heads = int(mcfg["num_heads"])
    kvw = int(mcfg.get("num_kv_heads") or heads) * (d // heads)
    out = {}
    for name, sub in tree.items():
        if isinstance(sub, dict) and "qkv" in sub:
            sub = dict(sub)
            qkv = sub.pop("qkv")
            for part, (lo, hi) in (("q", (0, d)), ("k", (d, d + kvw)),
                                   ("v", (d + kvw, d + 2 * kvw))):
                sub["qkv_" + part] = {k: v[..., lo:hi]
                                      for k, v in qkv.items()}
        out[name] = sub
    return out


def _norms(tree, mcfg):
    """Per-leaf L2 norms as a flat list of floats (one jitted call)."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in jax.tree_util.tree_leaves(
            split_fused(t, mcfg))])
    return [float(v) for v in jax.device_get(fn(tree))]


def _delta_norms(tree, base, mcfg):
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda a, b: [
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                    - y.astype(jnp.float32))))
        for x, y in zip(jax.tree_util.tree_leaves(split_fused(a, mcfg)),
                        jax.tree_util.tree_leaves(split_fused(b, mcfg)))])
    return [float(v) for v in jax.device_get(fn(tree, base))]


def _adam_mu(opt_state):
    """The first moment inside an optax state, wherever the chain put it."""
    import jax

    for node in jax.tree_util.tree_leaves(
            opt_state, is_leaf=lambda n: hasattr(n, "mu")):
        if hasattr(node, "mu"):
            return node.mu
    raise ValueError("no Adam first moment in the optimizer state")


def reference_steps(mcfg, tcfg, seed, batches, mode="f32", dtype=None):
    """The plain reference over the first steps: float32 AdamW on the
    reference's own loss and gradients. ``mode``/``dtype`` give the control
    (parameters and moments held in a lower precision). Returns losses, the
    first gradient's per-leaf norms and the per-leaf norms of the
    parameters' change after the last step."""
    import jax
    import jax.numpy as jnp

    dtype = dtype or jnp.float32
    lr, wd = float(tcfg["learning_rate"]), float(tcfg["weight_decay"])

    def update(p, mu, nu, g, t):
        def leaf(p_, m_, n_, g_):
            g_ = g_.astype(jnp.float32)
            m = B1 * m_.astype(jnp.float32) + (1 - B1) * g_
            n = B2 * n_.astype(jnp.float32) + (1 - B2) * g_ * g_
            mhat, nhat = m / (1 - B1 ** t), n / (1 - B2 ** t)
            pf = p_.astype(jnp.float32)
            new = pf - lr * (mhat / (jnp.sqrt(nhat) + ADAM_EPS) + wd * pf)
            return new.astype(dtype), m.astype(dtype), n.astype(dtype)

        out = jax.tree_util.tree_map(leaf, p, mu, nu, g)
        pick = lambda i: jax.tree_util.tree_map(
            lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
        return pick(0), pick(1), pick(2)

    update = jax.jit(update, static_argnums=(4,), donate_argnums=(0, 1, 2))
    cast = jax.jit(lambda t: jax.tree_util.tree_map(
        lambda a: a.astype(dtype), t))
    p = cast(weights.make_params(mcfg, seed, jnp.float32))
    mu = jax.tree_util.tree_map(jnp.zeros_like, p)
    nu = jax.tree_util.tree_map(jnp.zeros_like, p)
    losses, grad_norms = [], None
    for t, tokens in enumerate(batches, start=1):
        loss, g = reference.loss_and_grads(p, tokens, mcfg, mode=mode)
        losses.append(float(loss))
        if t == 1:
            grad_norms = _norms(g, mcfg)
        p, mu, nu = update(p, mu, nu, g, t)
        del g
    del mu, nu  # the seed's parameters again only now, so both fit
    p0 = weights.make_params(mcfg, seed, jnp.float32)
    delta = _delta_norms(p, p0, mcfg)
    del p, p0
    gc.collect()
    return {"loss": losses, "grad_norm": grad_norms, "delta_norm": delta}


def worst_leaf_gap(prog, ref, skip=None):
    """The widest gap between the program's norm and the reference's over
    the leaves, against the reference's norm of that leaf or of the median
    leaf, whichever is larger."""
    med = statistics.median(ref)
    worst, where = 0.0, -1
    for i, (a, b) in enumerate(zip(prog, ref)):
        if skip is not None and skip[i]:
            continue
        gap = abs(a - b) / max(b, med)
        if gap > worst:
            worst, where = gap, i
    return worst, where


def compare(prog, ref, limits, names):
    """Each number compared beside its limit."""
    med_g = statistics.median(ref["grad_norm"])
    # Leaves whose gradient is nought to rounding in the reference (under a
    # thousandth of the median leaf's) move under Adam by round-off alone:
    # left out of the change, by this rule and not by name.
    skip = [g < 1e-3 * med_g for g in ref["grad_norm"]]
    out = {}
    for i in range(len(ref["loss"])):
        gap = abs(prog["loss"][i] - ref["loss"][i]) / abs(ref["loss"][i])
        out[f"loss_step{i + 1}_gap"] = _cmp(gap, limits[f"loss_step{i + 1}_gap"])
    g_gap, g_at = worst_leaf_gap(prog["grad_norm"], ref["grad_norm"])
    d_gap, d_at = worst_leaf_gap(prog["delta_norm"], ref["delta_norm"], skip)
    out["grad_norm_gap"] = _cmp(g_gap, limits["grad_norm_gap"])
    out["delta_norm_gap"] = _cmp(d_gap, limits["delta_norm_gap"])
    where = {"grad_norm_gap_leaf": names[g_at], "delta_norm_gap_leaf":
             names[d_at], "leaves_skipped": int(sum(skip))}
    return out, where


def _cmp(value, limit):
    if limit is None:  # named in PERF.md as not compared
        return {"value": value, "limit": None, "ok": True}
    return {"value": value, "limit": limit, "ok": bool(value <= limit)}


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models.transformer import (
        TransformerConfig,
    )
    from distributed_tensorflow_tpu.parallel import data_parallel as dp
    from distributed_tensorflow_tpu.parallel.mesh import make_mesh
    from distributed_tensorflow_tpu.train.optimizers import make_optimizer

    devices = ctx["devices"]
    chips = len(devices)
    mcfg, tcfg = ctx["model_cfg"], ctx["train_cfg"]
    seconds = float(ctx["seconds"])
    seq = int(tcfg["seq_len"])
    batch = int(tcfg["batch_per_chip"]) * chips
    spans = common.Spans()

    t_phase = [time.time()]
    phases = {}

    def phase(name):
        t_phase.append(time.time())
        phases[name] = round(t_phase[-1] - t_phase[-2], 2)

    phases["to_runner"] = round(t_phase[0] - ctx["process_start"], 2)
    mesh = make_mesh(devices=devices)
    model_cfg = TransformerConfig(**mcfg, compute_dtype=jnp.bfloat16)
    tx = make_optimizer(tcfg["optimizer"], float(tcfg["learning_rate"]),
                        total_steps=1, weight_decay=float(tcfg["weight_decay"]))
    step = dp.build_lm_train_step(model_cfg, tx, mesh, donate=True)
    rep = lambda t: dp.replicate(t, mesh)
    params = rep(weights.make_params(mcfg, ctx["seed"], jnp.float32))
    names = _leaf_names(split_fused(params, mcfg))
    opt = jax.jit(tx.init)(params)
    g = rep(jnp.zeros((), jnp.int32))
    key = jax.random.PRNGKey(0)
    stream = traffic.BatchStream(ctx["traffic"], ctx["seed"], batch, seq,
                                 int(mcfg["vocab_size"]))

    class Feed:
        """The input pipeline: a batch made on the host and placed on the
        mesh, as ``train_lm.py``'s ``upload`` does."""

        def next_batch(self):
            host = stream.next()
            return host, dp.shard_global_batch(
                {"x": jnp.asarray(host)}, mesh)["x"]

    feed = Feed()
    spans.wrap(feed, "next_batch", "next_batch")
    phase("state_from_seed")
    fault = ctx.get("fault")

    def call(params, opt, g, tokens):
        if fault == "half_batch":  # the second half left out of the mean
            half = tokens.shape[0] // 2
            tokens = jnp.concatenate([tokens[:half], tokens[:half]])
        if fault == "state_unchanged":
            copy = jax.tree_util.tree_map(jnp.copy, (params, opt, g))
            _, _, _, m = step(*copy, tokens, key)
            return params, opt, g, m
        return step(params, opt, g, tokens, key)

    # ---- set-up: the first steps, through the window's own call and feed
    prog = {"loss": [], "grad_norm": None, "delta_norm": None}
    first_batches = []
    for t in range(1, CHECK_STEPS + 1):
        host, tokens = feed.next_batch()
        first_batches.append(host)
        params, opt, g, m = call(params, opt, g, tokens)
        prog["loss"].append(float(jax.device_get(m["loss"])))
        if t == 1:
            prog["grad_norm"] = [
                n / (1 - B1) for n in _norms(_adam_mu(opt), mcfg)]
    phase("first_steps")
    p0 = weights.make_params(mcfg, ctx["seed"], jnp.float32)
    prog["delta_norm"] = _delta_norms(params, rep(p0), mcfg)
    del p0
    gc.collect()
    phase("delta_norms")

    # ---- the window ------------------------------------------------------
    class Driver:
        def dispatch(self, params, opt, g, tokens):
            return call(params, opt, g, tokens)

        def drain(self, m):
            return float(jax.device_get(m["loss"]))

    driver = Driver()
    spans.wrap(driver, "dispatch", "dispatch")
    spans.wrap(driver, "drain", "drain")
    host, tokens = feed.next_batch()
    trace_dir, traced_steps, trace_s = None, 0, 0.0
    trace_steps = (int(ctx["traffic"].get("trace_steps", 3))
                   if ctx["trace"] else 0)
    t_open = time.perf_counter()
    setup_s = time.time() - ctx["process_start"]
    if trace_steps:
        trace_dir = common.start_trace(spans, ctx["scratch"])
    steps = 0
    inflight = []  # metrics of dispatched steps not yet drained
    last_loss = None
    while True:
        params, opt, g, m = driver.dispatch(params, opt, g, tokens)
        inflight.append(m)
        host, tokens = feed.next_batch()  # host work under the device's
        if len(inflight) > 1:
            last_loss = driver.drain(inflight.pop(0))
            steps += 1
            if trace_steps and steps >= trace_steps - 1:
                last_loss = driver.drain(inflight.pop(0))
                steps += 1
                jax.profiler.stop_trace()
                spans.annotate = False
                trace_s = time.perf_counter() - t_open
                traced_steps, trace_steps = steps, 0
            if time.perf_counter() - t_open >= seconds:
                break
    for m in inflight:  # the window is closed by a drain
        last_loss = driver.drain(m)
        steps += 1
    t_close = time.perf_counter()
    window_s = t_close - t_open
    peak = common.memory_peak_bytes(devices)
    tokens_done = steps * batch * seq
    e2e = {"train_tok_s": tokens_done / window_s, "setup_s": setup_s}
    collected = {
        "kind": "train", "window_s": window_s, "t_open": t_open,
        "t_close": t_close, "trace_s": trace_s, "spans": spans,
        "steps": steps, "traced_steps": traced_steps, "global_batch": batch,
        "seq": seq, "model_cfg": mcfg, "trace_dir": trace_dir,
    }

    # ---- correct: the reference follows the first three steps -----------
    del params, opt, g, m, inflight, tokens, step, driver
    gc.collect()
    t_chk = time.perf_counter()
    ref = reference_steps(mcfg, tcfg, ctx["seed"], first_batches)
    compared, where = compare(prog, ref, ctx["limits"], names)
    extra = dict(where, check_s=time.perf_counter() - t_chk,
                 steps=steps, window_s=window_s, last_loss=last_loss,
                 prog_loss=prog["loss"], ref_loss=ref["loss"],
                 setup_phases_s=phases)
    if ctx.get("control"):
        # the reference with its parameters and moments in bfloat16 stands
        # in the program's place, and goes through the same comparison
        ctl = reference_steps(mcfg, tcfg, ctx["seed"], first_batches,
                              mode="bf16", dtype=jnp.bfloat16)
        extra["program"] = {k: v["value"] for k, v in compared.items()}
        compared, extra["control_where"] = compare(ctl, ref, ctx["limits"],
                                                   names)
    return {
        "attempted": steps, "failed": 0, "end_to_end": e2e,
        "collected": collected, "compared": compared,
        "memory_peak_bytes": peak, "extra": extra,
    }
