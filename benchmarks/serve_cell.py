"""Runner of the serving cells: the stack ``tools/serve_lm.build_stack``
wires (scheduler -> SlotEngine -> paged KV and prefix cache -> the model's
cached branch), driven through ``Scheduler.submit`` from this process.

The HTTP front end is left out on purpose: in a deployment the client is on
another host, and a Python HTTP client in the server's process would charge
its own interpreter time to the server.
"""

from __future__ import annotations

import collections
import gc
import os
import sys
import threading
import time

import numpy as np

from benchmarks import common, reference, stats, traffic, weights


class Req:
    """One request as the client sees it."""

    __slots__ = ("spec", "due", "sent", "token_times", "tokens", "outcome",
                 "in_window", "started", "matched", "error")

    def __init__(self, spec, due=None):
        self.spec = spec
        self.due = due
        self.sent = None
        self.token_times = []
        self.tokens = []
        self.outcome = None
        self.in_window = False
        self.started = None  # engine.start entered (queue wait ends)
        self.matched = 0  # prompt tokens adopted from the prefix cache
        self.error = None

    @property
    def t_ref(self):
        """What the request is timed from: when it was due (open loop) or
        sent (closed loop)."""
        return self.due if self.due is not None else self.sent


class LoadGen:
    """Open- and closed-loop generators over ``Scheduler.submit``. A request
    is timed from when it was DUE (open loop) or sent (closed loop), and how
    late the generator sent it is recorded. Token times are taken where the
    client's stream handle yields them."""

    def __init__(self, scheduler, request_cls, plan, temperature, timeout_s):
        self.scheduler = scheduler
        self.request_cls = request_cls
        self.plan = plan
        self.temperature = float(temperature)
        self.timeout_s = float(timeout_s)
        self.by_prompt = {}
        self.requests = []
        self.lock = threading.Lock()
        self.stop = threading.Event()
        self.threads = []
        self.t0 = None

    def _submit_and_follow(self, req: Req):
        spec = req.spec
        with self.lock:
            self.by_prompt[id(spec["prompt"])] = req
            self.requests.append(req)
        req.sent = time.perf_counter()
        pending = self.scheduler.submit(self.request_cls(
            prompt=spec["prompt"], max_new_tokens=spec["max_new_tokens"],
            temperature=self.temperature, stream=True))
        try:
            for kind, payload in pending.stream_events(self.timeout_s):
                now = time.perf_counter()
                if kind == "tokens":
                    req.token_times.extend([now] * len(payload))
                    req.tokens.extend(payload)
                else:
                    req.outcome = payload
        except TimeoutError as exc:
            req.error = str(exc)

    def _client(self, pool, cursor):
        while not self.stop.is_set():
            with self.lock:
                i = cursor[0]
                cursor[0] += 1
            req = Req(pool[i % len(pool)])
            if i >= len(pool):  # a second lap needs fresh prompt objects
                req.spec = dict(req.spec, prompt=tuple(req.spec["prompt"]))
            self._submit_and_follow(req)

    def _dispatch(self, reqs):
        for spec in reqs:
            due = self.t0 + spec["due_s"]
            delay = due - time.perf_counter()
            if delay > 0 and self.stop.wait(delay):
                return
            if self.stop.is_set():
                return
            req = Req(spec, due=due)
            th = threading.Thread(target=self._submit_and_follow,
                                  args=(req,), daemon=True)
            th.start()
            self.threads.append(th)

    def start(self):
        self.t0 = time.perf_counter()
        if self.plan["loop"] == "closed":
            cursor = [0]
            for _ in range(self.plan["clients"]):
                th = threading.Thread(target=self._client,
                                      args=(self.plan["pool"], cursor),
                                      daemon=True)
                th.start()
                self.threads.append(th)
        else:
            reqs = self.plan["lead"] + self.plan["window"]
            th = threading.Thread(target=self._dispatch, args=(reqs,),
                                  daemon=True)
            th.start()
            self.threads.append(th)


def _install_spans(engine, spans: common.Spans, gen_ref: list):
    """Spans around the engine's bound methods; no file of the program is
    edited. ``engine.start`` also yields each request's queue wait and the
    prompt tokens its prefill adopted from the prefix cache."""

    def before_start(eng, args, kwargs):
        req = gen_ref[0].by_prompt.get(id(args[1])) if gen_ref[0] else None
        if req is not None:
            req.started = time.perf_counter()
        return (req, eng.stats["prefix_tokens_matched"])

    def after_start(eng, args, kwargs, out):
        return (eng.stats["prefix_tokens_matched"],)

    def before_decode(eng, args, kwargs):
        act = eng.active
        return (int(act.sum()), int(eng.lengths[act].sum()))

    spans.wrap(engine, "start", "engine.start", before_start, after_start)
    spans.wrap(engine, "step", "engine.step")
    spans.wrap(engine, "_advance_prefill", "engine.prefill")
    spans.wrap(engine, "_decode_round", "engine.decode", before_decode)


def _build(ctx):
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, os.path.join(common.ROOT, "tools"))
    try:
        from serve_lm import build_stack
    finally:
        sys.path.pop(0)
    from distributed_tensorflow_tpu.config import ServeConfig
    from distributed_tensorflow_tpu.models.transformer import (
        TransformerConfig,
    )

    mcfg = ctx["model_cfg"]
    params = weights.make_params(mcfg, ctx["seed"], jnp.bfloat16)
    jax.block_until_ready(params)
    tcfg = TransformerConfig(**mcfg, compute_dtype=jnp.bfloat16)
    serve_cfg = ServeConfig(**ctx["serve_cfg"])
    engine, scheduler, metrics, server = build_stack(serve_cfg, tcfg, params)
    return params, engine, scheduler, metrics, server


def _gap_stats(gaps, margins) -> dict:
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    if not g.size:
        return {"max": 0.0, "mean": 0.0, "noise_scale": 0.0, "off_best": 0.0,
                "n": 0}
    return {"max": float(g.max()), "mean": float(g.mean()),
            "noise_scale": stats.noise_scale(np.concatenate(margins), g),
            "off_best": float((g > 0).mean()), "n": int(g.size)}


def _gap_rows(params, mcfg, reqs, pad_to, control=None) -> dict:
    """At every served token of ``reqs``: the reference's margin (its best
    logit minus its second best) and the gap by which the served token's
    logit lies below the reference's best; with ``control`` also the gap of
    the token that this lower precision puts first at the same position.
    One list entry per request."""
    import jax.numpy as jnp

    step = -(-pad_to // 3 // 128) * 128  # three padded shapes at the most
    rows = {"margin": [], "served": [], "control": []}
    for r in reqs:
        prompt = list(r.spec["prompt"])
        served = list(r.tokens)
        seq = prompt + served[:-1]
        n = min(pad_to, -(-len(seq) // step) * step)
        padded = np.zeros(n, np.int32)
        padded[:len(seq)] = seq
        lo = len(prompt) - 1  # the row that predicts the first served token
        hi = lo + len(served)
        target = np.zeros(n, np.int32)
        target[lo:hi] = served
        lg = reference.logits(params, padded, mcfg)
        rows["served"].append(np.asarray(
            reference.gap_rows(lg, jnp.asarray(target)))[lo:hi])
        rows["margin"].append(np.asarray(reference.margin_rows(lg))[lo:hi])
        if control:
            lc = reference.logits(params, padded, mcfg, mode=control)
            rows["control"].append(np.asarray(
                reference.gap_rows(lg, lc.argmax(-1)))[lo:hi])
    return rows


def run(ctx) -> dict:
    import jax

    from distributed_tensorflow_tpu.serve.scheduler import (
        Completion,
        Request,
    )

    devices = ctx["devices"]
    traffic_cfg = ctx["traffic"]
    toy = ctx["toy"]
    seconds = float(ctx["seconds"])
    mcfg = ctx["model_cfg"]
    spans = common.Spans()
    gen_ref = [None]

    params, engine, scheduler, metrics, server = _build(ctx)
    _install_spans(engine, spans, gen_ref)
    compiled_warm = engine.compile_count()
    plan = traffic.serve_plan(traffic_cfg, ctx["seed"], seconds,
                              int(mcfg["vocab_size"]), toy=toy)
    t_cfg = traffic.apply_toy(traffic_cfg, toy)
    gen = LoadGen(scheduler, Request, plan, t_cfg.get("temperature", 0.0),
                  t_cfg.get("follow_timeout_s", 60.0))
    gen_ref[0] = gen
    fault = ctx.get("fault")
    if fault == "token_altered":  # one round's tokens in every 7 altered
        inner, rounds = engine.step, [0]

        def altered():
            toks, valid, done = inner()
            rounds[0] += 1
            if rounds[0] % 7 == 0:
                toks = (toks + 1) % int(mcfg["vocab_size"])
            return toks, valid, done

        engine.step = altered

    scheduler.start()
    gen.start()
    t_open = gen.t0 + plan["lead_s"]
    t_close = t_open + seconds
    time.sleep(max(0.0, t_open - time.perf_counter()))
    stats0 = dict(engine.stats)
    setup_s = time.time() - ctx["process_start"]
    trace_dir = None
    trace_s = 0.0
    if ctx["trace"]:
        trace_dir = common.start_trace(spans, ctx["scratch"])
        trace_s = min(float(t_cfg.get("trace_seconds", 3.0)), seconds)
        time.sleep(max(0.0, t_open + trace_s - time.perf_counter()))
        jax.profiler.stop_trace()
        spans.annotate = False
    time.sleep(max(0.0, t_close - time.perf_counter()))
    stats1 = dict(engine.stats)
    gen.stop.set()

    # Requests of the window: due inside it (open loop) or sent inside it
    # (closed loop). Each is followed to its first token after the close;
    # what is still decoding then is cut by the stop below and counts for
    # no rate, and as no failure.
    with gen.lock:
        all_reqs = list(gen.requests)
    for r in all_reqs:
        r.in_window = r.t_ref is not None and t_open <= r.t_ref < t_close
    window_reqs = [r for r in all_reqs if r.in_window]
    deadline = time.perf_counter() + float(
        t_cfg.get("follow_timeout_s", 60.0))
    while time.perf_counter() < deadline:
        if all(r.token_times or r.outcome is not None or r.error
               for r in window_reqs):
            break
        time.sleep(0.01)
    compiled_end = engine.compile_count()
    scheduler.stop()
    for th in gen.threads:
        th.join(timeout=10.0)
    server.server_close()
    peak = common.memory_peak_bytes(devices)

    # ---- the window's numbers -------------------------------------------
    ttft, itl, late, qwait = [], [], [], []
    failed = 0
    counted = stats.window_tokens(
        [(r.t_ref, r.spec["max_new_tokens"], r.token_times)
         for r in all_reqs], t_open, t_close, plan["loop"])
    for r in all_reqs:
        ts = r.token_times
        itl += [b - a for a, b in zip(ts, ts[1:]) if t_open <= b < t_close]
        if not r.in_window:
            continue
        if not ts:
            failed += 1
            continue
        ttft.append(ts[0] - r.t_ref)
        if r.due is not None:
            late.append(r.sent - r.due)
        if r.started is not None:
            qwait.append(r.started - r.sent)
    finished = [r for r in window_reqs
                if isinstance(r.outcome, Completion)
                and len(r.tokens) == len(r.outcome.tokens)]
    e2e = {
        "out_tok_s": counted["out_tokens"] / seconds,
        "itl_p95_ms": _ms(stats.percentile(itl, 95)),
        "ttft_p95_ms": _ms(stats.percentile(ttft, 95)),
        "setup_s": setup_s,
    }

    # ---- what the per-layer readers get ---------------------------------
    starts = spans.within("engine.start", t_open, t_close)
    prompt_spans = []
    for rec in starts:
        req, m_before, m_after = rec[2], rec[3], rec[4]
        if req is not None:
            req.matched = m_after - m_before
            prompt_spans.append((req.matched, len(req.spec["prompt"])))
    decodes = spans.within("engine.decode", t_open, t_close)
    collected = {
        "kind": "serve",
        "window_s": seconds,
        "t_open": t_open,
        "t_close": t_close,
        "trace_s": trace_s,
        "spans": spans,
        "client": {"ttft_s": ttft, "itl_s": itl, "lateness_s": late,
                   "queue_wait_s": qwait},
        "counters": {
            "prefix_tokens_matched": stats1["prefix_tokens_matched"]
            - stats0["prefix_tokens_matched"],
            "prefix_tokens_total": stats1["prefix_tokens_total"]
            - stats0["prefix_tokens_total"],
            "output_tokens": counted["delivered_tokens"],
            "prompt_spans": prompt_spans,
            "decode_rounds": [(r[0], r[1], r[2], r[3]) for r in decodes],
            "compiles_in_window": compiled_end - compiled_warm,
        },
        "model_cfg": mcfg,
        "trace_dir": trace_dir,
    }

    # ---- correct: after the window, the peak read, the state freed ------
    gen.scheduler = None
    del engine, scheduler, metrics, server, gen, gen_ref[0]
    gc.collect()
    rng = np.random.default_rng([int(ctx["seed"]), 0xC0DE])
    k = int(t_cfg.get("check_requests", 4))
    sample = []
    if finished:
        sample.append(max(finished, key=lambda r: len(r.spec["prompt"])
                          + len(r.tokens)))
        hit = max(finished, key=lambda r: r.matched)
        if hit.matched > 0 and hit not in sample:
            sample.append(hit)
        rest = [r for r in finished if r not in sample]
        for i in rng.permutation(len(rest))[: max(0, k - len(sample))]:
            sample.append(rest[int(i)])
    t_chk = time.perf_counter()
    longest = max(len(r["prompt"]) + r["max_new_tokens"] for r in (
        plan.get("pool") or plan["window"]))
    rows = _gap_rows(params, mcfg, sample, -(-longest // 256) * 256,
                     ctx.get("control"))
    check_s = time.perf_counter() - t_chk
    gap = _gap_stats(rows["served"], rows["margin"])
    gap_control = _gap_stats(rows["control"], rows["margin"])
    # ``--control``: the reference in the lower precision stands in the
    # program's place, and its numbers go through the same comparison.
    held = gap_control if ctx.get("control") else gap
    limits = ctx["limits"]
    compared = {
        "served_gap_max": _cmp(held["max"], limits["served_gap_max"]),
        "served_noise_scale": _cmp(held["noise_scale"],
                                   limits["served_noise_scale"]),
        "checked_tokens": _cmp(held["n"], limits["checked_tokens_min"],
                               at_least=True),
        "compiles_in_window": _cmp(compiled_end - compiled_warm, 0),
    }
    extra = {"check_s": check_s, "checked_requests": len(sample),
             "ttft_p50_ms": _ms(stats.percentile(ttft, 50)),
             "lateness_p95_ms": _ms(stats.percentile(late, 95)),
             "backlog_at_close": sum(
                 1 for r in window_reqs
                 if not r.token_times or r.token_times[0] >= t_close),
             "hit_rate": (collected["counters"]["prefix_tokens_matched"]
                          / max(1, collected["counters"]["prefix_tokens_total"])),
             "finished_in_window": len(finished),
             "hit_in_sample": max((r.matched for r in sample), default=0)}
    # What out_tok_s counted (stats.window_tokens), and what it left out.
    extra.update(counted)
    # Where the tail of the gaps lies, and what the engine's spans took:
    # what a reader of a far-off itl_p95_ms needs, on the earlier line only.
    extra["itl_n"] = len(itl)
    extra["itl_quantiles_ms"] = {
        str(q): _ms(stats.percentile(itl, q))
        for q in (50, 75, 90, 93, 94, 95, 96, 97, 98, 99)}
    hist = collections.Counter(int(g * 100) * 10 for g in itl)
    extra["itl_hist_10ms"] = {str(k): hist[k] for k in sorted(hist)}
    extra["span_p50_p95_ms_n"] = {
        name: [_ms(stats.percentile(d, 50)), _ms(stats.percentile(d, 95)),
               len(d)]
        for name in ("engine.start", "engine.prefill", "engine.decode",
                     "engine.step")
        for d in [[r[1] - r[0] for r in collected["spans"].within(
            name, t_open, t_close)]] if d}
    extra["served_gaps"] = gap
    if ctx.get("control"):
        extra["control_gaps"] = gap_control
    del params
    gc.collect()
    return {
        "attempted": len(window_reqs),
        "failed": failed,
        "end_to_end": e2e,
        "collected": collected,
        "compared": compared,
        "memory_peak_bytes": peak,
        "extra": extra,
        "check_rows": rows,
    }


def _ms(x):
    return None if x is None else 1000.0 * x


def _cmp(value, limit, at_least=False):
    ok = value >= limit if at_least else value <= limit
    return {"value": value, "limit": limit, "ok": bool(ok)}
