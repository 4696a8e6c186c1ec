#!/usr/bin/env python
"""Serve a TransformerLM over HTTP with continuous batching.

Loads a ``tools/train_lm.py`` params bundle (or ``--demo`` random-init
weights for smoke runs), warms up the slot engine (both jitted programs
compile before the port opens — no first-request compile stall), and runs
the ``serve/`` stack: FCFS scheduler on a background thread, stdlib HTTP
front end, TTFT/per-token metrics (optionally published to TensorBoard).

Example:
  python tools/serve_lm.py --model lm.msgpack --port 8000 --slots 8
  curl -s localhost:8000/generate -d '{"prompt": [7,8,9], "max_new_tokens": 16}'
  curl -s localhost:8000/metrics        # Prometheus text exposition
  curl -s localhost:8000/metrics.json   # JSON summary snapshot
  curl -s localhost:8000/healthz        # 200 serving / 503 shutting down
  curl -s localhost:8000/slo.json       # per-rule SLO state (--slo flag)

With ``--obs_dir DIR``: periodic Prometheus-text + JSONL snapshots of the
serving registry land in DIR, and any unhandled exception dumps the flight
recorder's last-N-events timeline there.

Byte-level bundles (vocab 256) also accept ``{"prompt": "text"}`` and
return decoded ``"text"`` alongside token ids.
"""

import argparse
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


class _ByteCodec:
    """String prompt <-> byte-level token ids for vocab-256 models."""

    def encode(self, text):
        from distributed_tensorflow_tpu.data.text import encode_text

        return [int(t) for t in encode_text(text)]

    def decode(self, tokens):
        from distributed_tensorflow_tpu.data.text import decode_tokens

        import numpy as np

        return decode_tokens(np.asarray(tokens, np.int32))


def build_stack(serve_cfg, cfg, params, deploy_cfg=None):
    """(engine, scheduler, metrics, http server) — warmed up, not started.
    Factored out so tests and loadgen --self-serve drive the same wiring
    as the CLI.

    The SLO monitor and recompile sentinel ride along as ``server.slo_monitor``
    / ``server.sentinel`` attributes (the 4-tuple is a published contract).
    The caller owns the monitor's ticker (``main()`` starts it; tests call
    ``evaluate()`` by hand).

    ``deploy_cfg`` (a ``config.DeployConfig``) adds the hot-swap plane:
    a VariantTable when canary/variant serving is configured, a
    WeightSwapper always, and a CheckpointWatcher when ``watch_dir`` is
    set — riding along as ``server.variant_table`` / ``server.swapper`` /
    ``server.watcher`` (None when absent). The caller starts/stops the
    watcher thread.

    The build is recorded as ``serve.build`` (``obs/trace.py``) around the
    spans ``serve.build_engine`` (the constructor, ``engine.place_weights``
    inside it) and ``engine.warmup``: what ``setup_s`` is made of. It is an
    interval closed at the end and no wrapper around this function: every
    Python frame above a jitted program's first call slows its tracing."""
    from distributed_tensorflow_tpu import obs

    obs.install_runtime_spans()
    build_t0 = time.monotonic()
    from distributed_tensorflow_tpu.serve import (
        Scheduler,
        ServingMetrics,
        ShardedSlotEngine,
        SlotEngine,
    )
    from distributed_tensorflow_tpu.serve.server import make_server

    metrics = ServingMetrics()
    # Poll mode on purpose: cache-size deltas are scoped to THIS engine's
    # programs, while the process-wide jax.monitoring listener would count
    # unrelated jit compiles (other engines, tests, train steps) as
    # serving recompiles.
    sentinel = obs.RecompileSentinel(metrics.registry, use_listener=False)
    draft_cfg = draft_params = None
    draft_path = getattr(serve_cfg, "draft_model", "")
    if draft_path:
        if not getattr(serve_cfg, "spec_k", 0):
            raise ValueError("--draft_model requires --spec_k > 0")
        from distributed_tensorflow_tpu.train.checkpoint import (
            load_lm_bundle,
        )

        draft_cfg, draft_params, _ = load_lm_bundle(draft_path)
    # --quant / --weight_dtype: weight-only quantized serving. A
    # pre-quantized bundle (tools/quantize_lm.py — its cfg already carries
    # weight_dtype) serves as-is; a high-precision one is quantized on the
    # fly. The drafter is quantized HARDER than the target (int4): drafter
    # rounding error only costs acceptance (extra verify rounds), never
    # output quality — the rejection-sampling verify step guarantees the
    # target distribution regardless of the drafter.
    quant = str(getattr(serve_cfg, "weight_dtype", "") or "")
    if quant or getattr(serve_cfg, "quant_group_size", 0):
        from dataclasses import replace

        from distributed_tensorflow_tpu.models.quant import (
            quantize_lm_params,
            validate_weight_quant,
        )

        gs = int(getattr(serve_cfg, "quant_group_size", 0))
        if quant == "int4" and not gs:
            gs = 64  # serving default; explicit --quant_group_size overrides
        if not getattr(cfg, "weight_dtype", None):
            tp_q = max(1, int(getattr(serve_cfg, "tp", 1)))
            validate_weight_quant(
                quant or None, gs, int(cfg.d_model), int(cfg.d_ff), tp=tp_q)
            cfg = replace(cfg, weight_dtype=quant, quant_group_size=gs)
            params = quantize_lm_params(
                params, quant, group_size=gs, hp_dtype=cfg.compute_dtype)
        if draft_params is not None and not getattr(
                draft_cfg, "weight_dtype", None):
            dgs = gs or 64
            validate_weight_quant(
                "int4", dgs, int(draft_cfg.d_model), int(draft_cfg.d_ff))
            draft_cfg = replace(
                draft_cfg, weight_dtype="int4", quant_group_size=dgs)
            draft_params = quantize_lm_params(
                draft_params, "int4", group_size=dgs,
                hp_dtype=cfg.compute_dtype)
    # --kv_dtype: the live KV-cache page format. '' keeps whatever the
    # bundle's model config says (e.g. --kv_cache_dtype below, or a
    # config that already bakes it in); 'bf16'/'int8' override it — the
    # same replace() discipline as --quant, so the engine's pool and
    # every jitted program see one consistent cfg.
    if hasattr(serve_cfg, "validate_kv"):
        serve_cfg.validate_kv()
    kv_override = getattr(serve_cfg, "engine_kv_cache_dtype", "keep")
    if kv_override != "keep":
        from dataclasses import replace

        if getattr(cfg, "kv_cache_dtype", None) != kv_override:
            cfg = replace(cfg, kv_cache_dtype=kv_override)
    # --tp N > 1: the SAME stack on a TP-partitioned model. Validate the
    # mesh against the model BEFORE any engine/jit work so a bad tp fails
    # with the config-level message, and build the sharded engine mode —
    # scheduler/server/fleet wiring below is byte-identical either way.
    tp = int(getattr(serve_cfg, "tp", 1))
    if tp > 1 and hasattr(serve_cfg, "validate_mesh"):
        serve_cfg.validate_mesh(cfg)
    engine_cls = SlotEngine if tp <= 1 else ShardedSlotEngine
    tp_kw = {} if tp <= 1 else {"tp": tp}
    with obs.span("serve.build_engine") as built:
        engine = engine_cls(
            cfg,
            params,
            **tp_kw,
            slots=serve_cfg.slots,
            max_len=serve_cfg.serve_max_len or None,
            prefill_len=serve_cfg.prefill_len or None,
            sentinel=sentinel,
            page_size=getattr(serve_cfg, "engine_page_size", None),
            kv_pages=getattr(serve_cfg, "kv_pages", 0),
            prefix_cache=getattr(serve_cfg, "prefix_cache", True),
            spec_k=getattr(serve_cfg, "spec_k", 0),
            spec_branches=getattr(serve_cfg, "spec_branches", 1),
            prefill_chunk_tokens=getattr(
                serve_cfg, "prefill_chunk_tokens", 0),
            draft_params=draft_params,
            draft_cfg=draft_cfg,
            draft_window=getattr(serve_cfg, "draft_window", 16),
        )
        built.note(decode_path=engine.decode_path,
                   decode_kernel_form=engine.decode_kernel_form,
                   prefill_path=engine.prefill_path)
    # What the build fixes (mesh width, bytes per device, dtype labels) is
    # mirrored to /metrics here, once: sync_engine never walks the params.
    metrics.bind_engine(engine)
    variants = swapper = watcher = None
    if deploy_cfg is not None:
        from distributed_tensorflow_tpu.serve.deploy import (
            CheckpointWatcher,
            VariantTable,
            WeightSwapper,
            make_canary_batch,
        )

        deploy_cfg.validate()
        if deploy_cfg.canary_percent > 0 or deploy_cfg.deploy_variant:
            variants = VariantTable(
                engine,
                canary_percent=deploy_cfg.canary_percent,
                canary_variant=deploy_cfg.canary_variant,
            )
        canary_batch = make_canary_batch(
            cfg.vocab_size,
            rows=deploy_cfg.canary_rows,
            length=min(deploy_cfg.canary_len, int(cfg.max_seq_len)),
        )
        swapper = WeightSwapper(
            engine,
            None,  # scheduler bound just below (it needs the table first)
            metrics=metrics,
            variants=variants,
            canary_batch=canary_batch,
            probe_prompts=[
                tuple(row[:8]) for row in
                canary_batch[:deploy_cfg.canary_probes]
            ],
            max_loss_ratio=deploy_cfg.max_loss_ratio,
        )
        # Compile the canary's eager executables against the live params
        # while the sentinel still counts compiles as warmup — the first
        # real swap must not breach the zero-recompile SLO.
        swapper.prewarm()
    engine.warmup()
    # Disaggregated tiers: a prefill-role replica gets a handoff outbox
    # (peers may arrive later via POST /admin/handoff_peers) and pushes
    # every slot to the decode tier at its first token; a decode-role
    # replica accepts imports on POST /handoff. "mixed" (default) is the
    # classic single-tier replica — no outbox, nothing changes.
    role = str(getattr(serve_cfg, "role", "mixed") or "mixed")
    handoff = None
    if role == "prefill":
        from distributed_tensorflow_tpu.serve.fleet.handoff import (
            HandoffOutbox,
        )

        handoff = HandoffOutbox(
            getattr(serve_cfg, "handoff_peer_list", ()),
            wire_version=int(getattr(serve_cfg, "handoff_wire", 2)),
            chunk_pages=int(getattr(serve_cfg, "handoff_chunk_pages", 4)),
            compress=bool(getattr(serve_cfg, "handoff_compress", True)),
            metrics=metrics,
        )
    scheduler = Scheduler(
        engine,
        max_queue_depth=serve_cfg.max_queue_depth,
        metrics=metrics,
        lane_weights=getattr(serve_cfg, "lane_weight_tuple", (8, 4, 1)),
        variants=variants,
        role=role,
        handoff=handoff,
    )
    if swapper is not None:
        swapper.scheduler = scheduler
        # The /admin/deploy handler only sees the scheduler — bind the
        # swapper there so fleet-pushed checkpoint steps reach the same
        # stage → boundary-canary → flip path the watcher uses.
        scheduler.swapper = swapper
        if deploy_cfg.enabled:
            target = deploy_cfg.deploy_variant or None
            watcher = CheckpointWatcher(
                deploy_cfg.watch_dir,
                lambda step, p: swapper.submit(step, p, variant=target),
                poll_interval_s=deploy_cfg.watch_interval_s,
                params_key=deploy_cfg.deploy_params_key,
            )
    slo_rules = obs.parse_slo_flag(
        getattr(serve_cfg, "slo", "default"),
        defaults=obs.default_serving_rules)
    slo_monitor = (obs.SloMonitor(metrics.registry, slo_rules)
                   if slo_rules else None)
    codec = _ByteCodec() if cfg.vocab_size == 256 else None
    server = make_server(
        scheduler,
        serve_cfg.host,
        serve_cfg.port,
        request_timeout_s=serve_cfg.request_timeout_s,
        codec=codec,
        slo=slo_monitor,
    )
    server.slo_monitor = slo_monitor
    server.sentinel = sentinel
    server.serving_metrics = metrics
    server.variant_table = variants
    server.swapper = swapper
    server.watcher = watcher
    obs.trace.interval("serve.build", build_t0, time.monotonic(),
                       slots=int(serve_cfg.slots))
    return engine, scheduler, metrics, server


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", default="lm.msgpack")
    parser.add_argument(
        "--demo", action="store_true",
        help="serve random-init weights (no bundle needed; smoke/loadgen)",
    )
    parser.add_argument("--seq_len", type=int, default=128)
    parser.add_argument("--vocab_size", type=int, default=256)
    parser.add_argument("--d_model", type=int, default=128)
    parser.add_argument("--num_heads", type=int, default=4)
    parser.add_argument("--num_layers", type=int, default=4)
    parser.add_argument("--d_ff", type=int, default=512)
    parser.add_argument(
        "--kv_cache_dtype", default="", choices=("", "int8"),
        help="KV-pool storage dtype ('' = compute dtype)",
    )
    parser.add_argument(
        "--quant", default="", choices=("", "int8", "int4"),
        help="weight-only quantization (alias for --weight_dtype; a "
        "pre-quantized bundle serves as-is, a high-precision one is "
        "quantized on the fly; the drafter is quantized harder: int4)",
    )
    args, rest = parser.parse_known_args(argv)

    from distributed_tensorflow_tpu.config import (
        DeployConfig,
        ServeConfig,
        parse_flags,
    )

    serve_cfg, deploy_cfg = parse_flags(ServeConfig, DeployConfig, argv=rest)
    if args.quant:
        serve_cfg.weight_dtype = args.quant

    import jax
    import jax.numpy as jnp

    if args.demo:
        from distributed_tensorflow_tpu.models.transformer import (
            TransformerConfig,
            TransformerLM,
            default_compute_dtype,
        )

        cfg = TransformerConfig(
            vocab_size=args.vocab_size,
            d_model=args.d_model,
            num_heads=args.num_heads,
            num_layers=args.num_layers,
            d_ff=args.d_ff,
            max_seq_len=args.seq_len,
            compute_dtype=default_compute_dtype(),
        )
        params = TransformerLM(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
    else:
        from distributed_tensorflow_tpu.train.checkpoint import load_lm_bundle

        try:
            cfg, params, _ = load_lm_bundle(
                args.model,
                fallback_shapes={
                    "vocab_size": args.vocab_size,
                    "d_model": args.d_model,
                    "num_heads": args.num_heads,
                    "num_layers": args.num_layers,
                    "d_ff": args.d_ff,
                    "max_seq_len": args.seq_len,
                },
            )
        except ValueError as e:
            sys.exit(str(e))
    if args.kv_cache_dtype:
        from dataclasses import replace

        cfg = replace(cfg, kv_cache_dtype=args.kv_cache_dtype)

    engine, scheduler, metrics, server = build_stack(
        serve_cfg, cfg, params, deploy_cfg=deploy_cfg)
    host, port = server.server_address
    if server.watcher is not None:
        print(
            f"deploy: watching {deploy_cfg.watch_dir} every "
            f"{deploy_cfg.watch_interval_s}s "
            f"(variant={deploy_cfg.deploy_variant or '<live>'} "
            f"canary={deploy_cfg.canary_percent}%)",
            flush=True,
        )
    kv_desc = (
        f"paged(page_size={engine.page_size} pages={engine.pool.num_pages} "
        f"prefix={'on' if engine.prefix is not None else 'off'} "
        f"spec_k={engine.spec_k} spec_branches={engine.spec_branches} "
        f"drafter={engine.drafter} kv_dtype={engine.kv_dtype} "
        f"chunk={engine.prefill_chunk_tokens})"
    )
    print(
        f"serving on http://{host}:{port}  slots={engine.slots} "
        f"max_len={engine.max_len} prefill_len={engine.prefill_len} "
        f"kv={kv_desc} mesh=tp{engine.tp}x{engine.mesh_device_count}dev "
        f"weights={engine.weight_dtype} role={scheduler.role} "
        f"compiled={engine.compile_count()}",
        flush=True,
    )

    obs_export = None
    if serve_cfg.obs_dir:
        from distributed_tensorflow_tpu import obs
        from distributed_tensorflow_tpu.obs import export as obs_export

        obs.set_dump_dir(serve_cfg.obs_dir)
        obs.install_excepthook()

    def export_obs():
        if obs_export is None:
            return
        obs_export.write_jsonl_snapshot(
            os.path.join(serve_cfg.obs_dir, "serve_metrics.jsonl"),
            metrics.registry,
        )
        prom_path = os.path.join(serve_cfg.obs_dir, "serve_metrics.prom")
        with open(prom_path, "w") as f:
            f.write(obs_export.prometheus_text(metrics.registry))
        # Fleet plane: mergeable per-process snapshot next to the human
        # exports, so a shared obs_dir across replicas aggregates.
        from distributed_tensorflow_tpu.obs import aggregate as obs_aggregate

        obs_aggregate.write_process_snapshot(
            serve_cfg.obs_dir, metrics.registry)

    writer = None
    pub_step = [0]
    if serve_cfg.serve_log_dir or obs_export is not None:
        if serve_cfg.serve_log_dir:
            from distributed_tensorflow_tpu.utils.summary import SummaryWriter

            writer = SummaryWriter(serve_cfg.serve_log_dir)

        def publish_loop():
            while True:
                time.sleep(serve_cfg.metrics_interval_s)
                pub_step[0] += 1
                if writer is not None:
                    metrics.publish(writer, pub_step[0])
                    writer.flush()
                export_obs()

        threading.Thread(
            target=publish_loop, name="serve-metrics", daemon=True
        ).start()

    scheduler.start()
    if server.slo_monitor is not None:
        server.slo_monitor.start(serve_cfg.slo_interval_s)
    if server.watcher is not None:
        server.watcher.start()

    # SIGTERM = graceful drain (the fleet contract): stop accepting so
    # /healthz flips 503 and the router marks this replica draining, keep
    # serving everything already accepted, then stop when idle or when the
    # drain deadline expires — whichever comes first.
    import signal

    def _on_sigterm(signum, frame):
        scheduler.begin_drain(serve_cfg.drain_deadline_s)
        print(
            f"serve_lm: SIGTERM — draining for up to "
            f"{serve_cfg.drain_deadline_s}s",
            flush=True,
        )

        def _finish():
            deadline = time.monotonic() + serve_cfg.drain_deadline_s
            while time.monotonic() < deadline and not scheduler.idle:
                time.sleep(0.05)
            server.shutdown()

        threading.Thread(target=_finish, name="serve-drain",
                         daemon=True).start()

    signal.signal(signal.SIGTERM, _on_sigterm)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        server.shutdown()
        if server.watcher is not None:
            server.watcher.stop()
        if server.slo_monitor is not None:
            server.slo_monitor.stop()
        scheduler.stop()
        if getattr(scheduler, "handoff", None) is not None:
            scheduler.handoff.stop()
        if writer is not None:
            metrics.publish(writer, pub_step[0] + 1)
            writer.close()
        export_obs()  # final scrape survives the shutdown
        print("serve_lm: shut down cleanly", flush=True)


if __name__ == "__main__":
    main()
