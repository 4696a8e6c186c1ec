#!/usr/bin/env python
"""Long-context transformer-LM training CLI — the driveable consumer of the
framework's parallelism stack. Selectable strategy:

  --parallelism dp    data parallelism only (model axis unused)
  --parallelism sp    sequence parallelism: sequence sharded over 'model',
                      ring attention via ppermute (long contexts)
  --parallelism tp    Megatron tensor parallelism: heads/FFN over 'model'
  --parallelism pp    GPipe pipeline parallelism: layer stages over 'model'
  --parallelism ep    switch-MoE expert parallelism: --num_experts experts
                      sharded over 'model', all_to_all token exchange
  --parallelism fsdp  ZeRO-3: params + Adam moments sharded 1/N per device,
                      all_gather on use, psum_scatter for grads
  --parallelism 3d    DP x PP x TP on a ('data','pipe','model') mesh:
                      --pipeline_parallel stages of --model_parallel-way
                      Megatron blocks under the GPipe schedule
  --parallelism sp_tp DP x SP x TP: sequence sharded over 'pipe' with ring
                      attention, heads/FFN over 'model' — the
                      long-context-at-scale shape (--pipeline_parallel
                      sets the sequence-shard count)

Data: ``--text_file`` trains byte-level (vocab 256) on any file via random
windows (`data/text.py`; a holdout tail is reserved for tools/eval_lm.py);
without it, a synthetic copy-structured token stream (deterministic,
learnable — this environment has no corpora). One JSON line per eval
interval; final params exported as an inference bundle.

Example (8-device CPU mesh):
  JAX_PLATFORMS=cpu \\
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
    python tools/train_lm.py --parallelism tp --model_parallel 2 \\
      --training_steps 50 --seq_len 128
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def synthetic_tokens(rng, batch, seq_len, vocab):
    """Copy task: second half repeats the first half — next-token prediction
    on the second half is learnable, loss floor well below uniform."""
    import numpy as np

    half = seq_len // 2
    first = rng.integers(2, vocab, (batch, half))
    return np.concatenate([first, first], axis=1).astype(np.int32)


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument(
        "--parallelism",
        choices=("dp", "sp", "tp", "pp", "ep", "fsdp", "3d", "sp_tp"),
        default="dp",
    )
    parser.add_argument("--num_experts", type=int, default=4, help="ep only")
    parser.add_argument("--model_parallel", type=int, default=1)
    parser.add_argument(
        "--pipeline_parallel", type=int, default=1,
        help="size of the 'pipe' mesh axis: pipeline stages (3d) or "
             "sequence shards (sp_tp)",
    )
    parser.add_argument("--training_steps", type=int, default=100)
    parser.add_argument("--eval_step_interval", type=int, default=10)
    parser.add_argument("--batch_size", type=int, default=8, help="global batch")
    parser.add_argument("--seq_len", type=int, default=128)
    parser.add_argument(
        "--text_file", default="",
        help="train byte-level (vocab 256) on this file instead of the "
             "synthetic stream; a holdout tail is reserved for eval_lm.py",
    )
    parser.add_argument("--holdout_fraction", type=float, default=0.05)
    parser.add_argument("--vocab_size", type=int, default=256)
    parser.add_argument("--d_model", type=int, default=128)
    parser.add_argument("--num_heads", type=int, default=4)
    parser.add_argument(
        "--num_kv_heads", type=int, default=0,
        help="grouped-query attention: K/V heads shared by query groups "
             "(0 = multi-head; shrinks the KV cache and kv projections)",
    )
    parser.add_argument(
        "--attention_window", type=int, default=0,
        help="sliding-window causal attention: each token attends the "
             "previous N positions only (0 = full causal; the flash "
             "kernels skip out-of-window blocks, O(S*window) cost)",
    )
    parser.add_argument(
        "--position", default="learned", choices=("learned", "rope"),
        help="position encoding: learned additive table (historical "
             "default) or rotary embeddings (RoPE — no position table, "
             "relative offsets in the q/k dot product, sequence-length "
             "extrapolation)",
    )
    parser.add_argument(
        "--rope_theta", type=float, default=10000.0,
        help="RoPE rotation base (only with --position rope; larger bases "
             "slow the angular frequencies for longer contexts)",
    )
    parser.add_argument(
        "--use_bias", type=int, default=1, choices=(0, 1),
        help="Dense-layer biases (1 = biased, the historical default; 0 = "
             "bias-free, the modern-LM convention the bench flagship uses — "
             "worth ~2%% of a step: XLA emits each bias gradient as a "
             "separate unfused whole-activation reduce)",
    )
    parser.add_argument("--num_layers", type=int, default=4)
    parser.add_argument("--d_ff", type=int, default=512)
    parser.add_argument("--learning_rate", type=float, default=3e-3)
    parser.add_argument("--optimizer", default="adam",
                        choices=("adam", "adamw", "sgd", "momentum"))
    parser.add_argument("--lr_schedule", default="constant",
                        choices=("constant", "cosine", "warmup_cosine", "linear"))
    parser.add_argument("--warmup_steps", type=int, default=0)
    parser.add_argument("--grad_clip_norm", type=float, default=0.0)
    parser.add_argument("--attention", default="dense",
                        choices=("dense", "blockwise", "flash"))
    parser.add_argument(
        "--remat", action="store_true",
        help="rematerialise transformer blocks on backward (activation "
             "memory O(L*S*d_model) instead of every intermediate)",
    )
    parser.add_argument("--num_microbatches", type=int, default=2, help="pp only")
    parser.add_argument(
        "--steps_per_call", type=int, default=1,
        help="dp only: fuse k optimizer steps into one XLA dispatch "
             "(lax.scan over stacked batches) — amortizes per-dispatch "
             "runtime latency; semantics identical to k single steps",
    )
    parser.add_argument("--output", default="", help="optional params bundle path")
    parser.add_argument(
        "--train_dir", default="",
        help="checkpoint dir: timed autosave + resume (any parallelism mode)",
    )
    parser.add_argument("--save_secs", type=int, default=600)
    parser.add_argument(
        "--profile_dir", default="",
        help="write a jax.profiler (TensorBoard XPlane) trace here",
    )
    parser.add_argument(
        "--obs_dir", default="",
        help="observability output dir: per-boundary metrics.jsonl + "
             "per-process fleet_p<i>.json snapshots (chief merges them to "
             "fleet_merged.prom/json) + flight-recorder crash dumps "
             "(unhandled exceptions dump the last-N-events timeline here)",
    )
    parser.add_argument(
        "--slo", default="",
        help="SLO rules evaluated at eval boundaries (needs --obs_dir): "
             "'default' (step time, data-wait), 'off', and/or "
             "comma-separated 'metric[:agg]>thr[@sustain][#name]' specs",
    )
    parser.add_argument("--profile_start_step", type=int, default=5)
    parser.add_argument("--profile_num_steps", type=int, default=3)
    parser.add_argument("--seed", type=int, default=0)
    # Reference-style cluster flags (demo2 parity): worker_hosts[0] is the
    # jax.distributed coordinator, task_index the process id.
    parser.add_argument("--worker_hosts", default="localhost:12355")
    parser.add_argument("--task_index", type=int, default=0)
    parser.add_argument("--job_name", default="worker")
    args, _ = parser.parse_known_args(argv)
    if args.steps_per_call > 1 and args.parallelism != "dp":
        sys.exit("--steps_per_call > 1 is only supported with --parallelism dp")
    if args.steps_per_call < 1:
        sys.exit("--steps_per_call must be >= 1")
    from distributed_tensorflow_tpu.utils.compile_cache import (
        enable_compilation_cache,
    )

    enable_compilation_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from distributed_tensorflow_tpu.config import ClusterConfig
    from distributed_tensorflow_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
        default_compute_dtype,
    )
    from distributed_tensorflow_tpu.parallel import data_parallel as dp, distributed
    from distributed_tensorflow_tpu.parallel.mesh import make_mesh
    from distributed_tensorflow_tpu.utils.timer import StepTimer

    obs = None
    if args.obs_dir:
        from distributed_tensorflow_tpu import obs
        from distributed_tensorflow_tpu.obs import export as obs_export

        obs.set_dump_dir(args.obs_dir)
        obs.install_excepthook()
        obs_reg = obs.get_registry()
        obs_loss = obs_reg.gauge("lm_loss", "Training loss at the last eval boundary.")
        obs_rate = obs_reg.gauge(
            "lm_tokens_per_sec", "Tokens/s over the last drained window.")
        obs_steps = obs_reg.counter("lm_steps_total", "Optimizer steps completed.")
        obs_perf = obs.PerfGauges(obs_reg)
        slo_rules = obs.parse_slo_flag(
            args.slo, defaults=obs.default_training_rules)
        slo_monitor = obs.SloMonitor(obs_reg, slo_rules) if slo_rules else None

    cluster = ClusterConfig(
        worker_hosts=args.worker_hosts,
        task_index=args.task_index,
        job_name=args.job_name,
    )
    if not distributed.initialize_from_cluster(cluster):
        return None  # ps role: nothing to do on TPU
    chief = distributed.is_chief()

    if args.text_file:
        from distributed_tensorflow_tpu.data.text import (
            ByteTextDataset,
            load_byte_tokens,
        )

        # Same seed on every process: batches are a pure function of
        # (seed, step), every process generates the IDENTICAL global batch
        # and shard_global_batch serves each device its own index slice of
        # it — so a run's data schedule is independent of the process count.
        text_data = ByteTextDataset(
            load_byte_tokens(args.text_file),
            args.seq_len,
            holdout_fraction=args.holdout_fraction,
            seed=args.seed,
        )
        args.vocab_size = 256  # bytes
    else:
        text_data = None

    if args.parallelism in ("3d", "sp_tp"):
        from distributed_tensorflow_tpu.parallel.mesh import make_mesh3

        mesh = make_mesh3(
            pipeline_parallel=args.pipeline_parallel,
            model_parallel=args.model_parallel,
        )
    else:
        mesh = make_mesh(model_parallel=args.model_parallel)
    cfg = TransformerConfig(
        vocab_size=args.vocab_size,
        d_model=args.d_model,
        num_heads=args.num_heads,
        num_kv_heads=args.num_kv_heads or None,
        attention_window=args.attention_window or None,
        use_bias=bool(args.use_bias),
        position=args.position,
        rope_theta=args.rope_theta,
        num_layers=args.num_layers,
        d_ff=args.d_ff,
        max_seq_len=args.seq_len,
        attention=args.attention,
        remat=args.remat,
        compute_dtype=default_compute_dtype(),
    )
    from distributed_tensorflow_tpu.train.optimizers import make_optimizer

    tx = make_optimizer(
        args.optimizer,
        args.learning_rate,
        total_steps=args.training_steps,
        schedule=args.lr_schedule,
        warmup_steps=args.warmup_steps,
        grad_clip_norm=args.grad_clip_norm,
    )
    rng = np.random.default_rng(args.seed)
    rep = lambda t: dp.replicate(t, mesh)
    g0 = rep(jnp.zeros((), jnp.int32))

    if args.parallelism == "ep":
        from distributed_tensorflow_tpu.parallel import expert_parallel as epx

        host = epx.init_moe_lm_params(cfg, num_experts=args.num_experts, seed=args.seed)
        step = epx.build_moe_lm_train_step(
            cfg, args.num_experts, tx, mesh, host, donate=False
        )
        params = epx.shard_moe_params(host, mesh)
        opt = epx.shard_moe_params(jax.device_get(tx.init(host)), mesh)
        place = lambda t: dp.shard_global_batch({"x": t}, mesh)["x"]
    elif args.parallelism == "tp":
        from distributed_tensorflow_tpu.parallel import tensor_parallel as tp

        host = tp.init_tp_params(cfg, seed=args.seed)
        step = tp.build_tp_lm_train_step(cfg, tx, mesh, host, donate=False)
        params = tp.shard_params(host, mesh)
        opt = tp.shard_params(jax.device_get(tx.init(host)), mesh)
        place = lambda t: dp.shard_global_batch({"x": t}, mesh)["x"]
    elif args.parallelism == "pp":
        from distributed_tensorflow_tpu.parallel import pipeline_parallel as pp

        plain = jax.device_get(
            TransformerLM(cfg).init(
                jax.random.PRNGKey(args.seed), jnp.zeros((1, 8), jnp.int32)
            )["params"]
        )
        stacked = pp.stack_stage_params(plain, num_stages=args.model_parallel)
        step = pp.build_pp_lm_train_step(
            cfg, tx, mesh, stacked, num_microbatches=args.num_microbatches, donate=False
        )
        params = pp.shard_pp_params(stacked, mesh)
        opt = pp.shard_pp_params(jax.device_get(tx.init(stacked)), mesh)
        place = lambda t: dp.shard_global_batch({"x": t}, mesh)["x"]
    elif args.parallelism == "3d":
        from distributed_tensorflow_tpu.parallel import three_d as td

        host = td.init_3d_params(cfg, num_stages=args.pipeline_parallel, seed=args.seed)
        step = td.build_3d_lm_train_step(
            cfg, tx, mesh, host, num_microbatches=args.num_microbatches, donate=False
        )
        params = td.shard_3d_params(host, mesh)
        opt = td.shard_3d_params(jax.device_get(tx.init(host)), mesh)
        place = lambda t: dp.shard_global_batch({"x": t}, mesh, spec=P("data", None))["x"]
    elif args.parallelism == "sp_tp":
        from distributed_tensorflow_tpu.parallel import tensor_parallel as tpmod
        from distributed_tensorflow_tpu.parallel import three_d as td

        host = tpmod.init_tp_params(cfg, seed=args.seed)
        step = td.build_sp_tp_lm_train_step(cfg, tx, mesh, host, donate=False)
        params = tpmod.shard_params(host, mesh)
        opt = tpmod.shard_params(jax.device_get(tx.init(host)), mesh)
        place = lambda t: dp.shard_global_batch({"x": t}, mesh, spec=P("data", "pipe"))[
            "x"
        ]
    elif args.parallelism == "fsdp":
        from distributed_tensorflow_tpu.parallel import fsdp

        host = jax.device_get(
            TransformerLM(cfg).init(
                jax.random.PRNGKey(args.seed), jnp.zeros((1, 8), jnp.int32)
            )["params"]
        )
        step = fsdp.build_fsdp_lm_train_step(cfg, tx, mesh, host, donate=False)
        params = fsdp.shard_fsdp_params(host, mesh)
        opt = fsdp.init_fsdp_opt_state(tx, host, mesh)
        place = lambda t: dp.shard_global_batch({"x": t}, mesh)["x"]
    elif args.parallelism == "sp":
        from distributed_tensorflow_tpu.parallel import sequence_parallel as sp

        plain = jax.device_get(
            TransformerLM(cfg).init(
                jax.random.PRNGKey(args.seed), jnp.zeros((1, 8), jnp.int32)
            )["params"]
        )
        step = sp.build_lm_train_step(cfg, tx, mesh, donate=False)
        params = rep(plain)
        opt = rep(jax.device_get(tx.init(plain)))
        place = lambda t: sp.shard_lm_batch(t, mesh)
    else:  # dp
        plain = jax.device_get(
            TransformerLM(cfg).init(
                jax.random.PRNGKey(args.seed), jnp.zeros((1, 8), jnp.int32)
            )["params"]
        )
        # Donated param/opt buffers: the loop rebinds them every step and
        # never touches the old copies; donation frees them during the
        # step (measured: 61 -> 64% MFU at the bench flagship shape, and
        # batch headroom — BASELINE.md).
        step = dp.build_lm_train_step(cfg, tx, mesh, donate=True)
        params = rep(plain)
        opt = rep(jax.device_get(tx.init(plain)))
        place = lambda t: dp.shard_global_batch({"x": t}, mesh)["x"]

    g = g0
    ckpt = None
    if args.train_dir:
        from distributed_tensorflow_tpu.train.checkpoint import (
            CheckpointManager,
            coordinated_maybe_save,
        )

        ckpt = CheckpointManager(args.train_dir, save_interval_secs=args.save_secs)
        # TP/PP/EP states carry sharded leaves; restore host-side then
        # re-place with the mode's own placement (params/opt were placed
        # above, so reuse their shardings leaf-by-leaf).
        template = {"params": params, "opt_state": opt, "global_step": g}
        restored = ckpt.restore_latest(template)
        if restored is not None:
            latest, state = restored

            def replace(cur, new):
                # Cross-process-sharded leaves come back already placed
                # (Orbax restored each process's shards); host leaves are
                # re-placed with the mode's own sharding.
                if isinstance(new, jax.Array):
                    return new
                return jax.device_put(np.asarray(new), cur.sharding)

            params, opt, g = (
                jax.tree_util.tree_map(replace, template[k], state[k])
                for k in ("params", "opt_state", "global_step")
            )
            if chief:
                print(f"restored checkpoint at step {latest} from {args.train_dir}")

    start = int(jax.device_get(g))
    # Boundary-drained timing: ticks happen ONLY after the boundary's
    # device_get (which forces completion of every queued dispatch) —
    # dispatch is asynchronous, so per-dispatch ticks measure issue time,
    # not compute, and inflate steps/s wildly.
    # warmup=2: the first timed window (contains the jit compile) is
    # excluded along with the pre-loop mark.
    timer = StepTimer(warmup_steps=2)
    timer.start(start)
    key = jax.random.PRNGKey(args.seed)
    m = {"loss": jnp.nan}  # resume-at-completion runs zero steps
    # TensorBoard events alongside the checkpoints (chief only) — the same
    # observability the MNIST trainer has (utils/summary.py).
    writer = None
    if args.train_dir and chief:
        from distributed_tensorflow_tpu.utils.summary import SummaryWriter

        writer = SummaryWriter(args.train_dir)
    from distributed_tensorflow_tpu.utils.profiler import Profiler

    prof = Profiler(
        args.profile_dir if chief else None,
        start_step=start + args.profile_start_step,
        num_steps=args.profile_num_steps,
        sync=lambda: jax.device_get(g),
    )
    def batch_for(i):
        if text_data is not None:
            # Step-keyed windows: resume at step i draws exactly what an
            # uninterrupted run would have drawn at step i.
            return text_data.train_batch(args.batch_size, step=i)
        return synthetic_tokens(rng, args.batch_size, args.seq_len, args.vocab_size)

    # Chunk schedule: runs of --steps_per_call fused steps, split at eval
    # boundaries so reporting/checkpoint cadence is unchanged (one compiled
    # program per distinct run length, like the MNIST trainer).
    def chunk_schedule():
        i, interval, total = start, args.eval_step_interval, args.training_steps
        while i < total:
            nxt = min(total, (i // interval + 1) * interval)
            k_eff = min(args.steps_per_call, nxt - i)
            yield i, k_eff
            i += k_eff

    # One builder serves every chunk length: the scan reads k from the
    # stacked batch shape, and jit's shape-keyed cache compiles one program
    # per distinct length on first use.
    multi_step = (
        dp.build_lm_multi_step(cfg, tx, mesh, donate=True)
        if args.parallelism == "dp" and args.steps_per_call > 1
        else None
    )

    from jax.sharding import PartitionSpec as _P

    def upload(i, k_eff):
        if k_eff == 1:
            return place(jnp.asarray(batch_for(i)))
        stacked = np.stack([batch_for(j) for j in range(i, i + k_eff)])
        return dp.shard_global_batch(
            {"x": jnp.asarray(stacked)}, mesh, spec=_P(None, ("data", "model"), None)
        )["x"]

    try:
      # Software-pipelined input: the next chunk's batch is built and
      # uploaded WHILE the (asynchronously dispatched) current chunk
      # computes, instead of a serial per-step device_put sitting between
      # two steps (the LM analog of data/prefetch.py).
      # One-ahead iteration keeps memory O(1) for million-step schedules.
      sched_it = chunk_schedule()
      cur = next(sched_it, None)
      tokens = upload(*cur) if cur is not None else None
      while cur is not None:
        i, k_eff = cur
        with prof.step(i, span=k_eff):
            run = step if k_eff == 1 else multi_step
            params, opt, g, m = run(params, opt, g, tokens, key)
        nxt = next(sched_it, None)
        if nxt is not None:
            tokens = upload(*nxt)
        i_end = i + k_eff
        boundary = i_end % args.eval_step_interval == 0 or i_end == args.training_steps
        if boundary:
            step_now = int(jax.device_get(g))  # completion barrier
            # Fused chunks return stacked (k,) losses; report the last step's.
            loss_now = float(np.asarray(jax.device_get(m["loss"])).reshape(-1)[-1])
            timer.tick_to(step_now)
            tokens_per_sec = timer.steps_per_sec * args.batch_size * args.seq_len
            # Compute-efficiency observability (same accounting as bench.py):
            # model FLOPs / elapsed / cluster bf16 peak. None off-TPU or for
            # MoE (its FLOPs depend on routing, not cfg alone).
            mfu = None
            if args.parallelism != "ep":
                from distributed_tensorflow_tpu.utils.flops import (
                    chip_peak_flops,
                    transformer_train_flops,
                )

                peak = chip_peak_flops()
                if peak is not None:
                    flops = transformer_train_flops(cfg, args.batch_size)
                    mfu = round(
                        flops * timer.steps_per_sec / (peak * len(jax.devices())), 4
                    )
            scalars = {"loss": loss_now}
            if timer.steps_per_sec > 0:  # first drained window = compile
                scalars["steps_per_sec"] = timer.steps_per_sec
                if mfu is not None:
                    scalars["mfu"] = mfu
            if writer is not None:
                writer.add_scalars(scalars, step_now)
            if obs is not None:
                obs_loss.set(loss_now)
                obs_steps.inc(max(step_now - start - int(obs_steps.value), 0))
                if timer.steps_per_sec > 0:
                    obs_rate.set(tokens_per_sec)
                    # Live MFU/roofline plane: the same arithmetic as the
                    # stdout record above, but as scrape-able gauges
                    # (train_mfu stays unset off-TPU — graceful null).
                    obs_perf.update_window(
                        steps_per_sec=timer.steps_per_sec,
                        tokens_per_step=args.batch_size * args.seq_len,
                        examples_per_step=args.batch_size,
                        model_cfg=cfg if args.parallelism != "ep" else None,
                        batch_size=args.batch_size,
                    )
                obs.update_memory_gauges()
                if slo_monitor is not None:
                    slo_monitor.evaluate()
                obs.write_process_snapshot(args.obs_dir)
                if chief:
                    obs_export.write_jsonl_snapshot(
                        os.path.join(args.obs_dir, "metrics.jsonl")
                    )
                    agg = obs.FleetAggregator()
                    if agg.load_dir(args.obs_dir):
                        agg.export(args.obs_dir)
            if chief:
                record = {
                    "step": step_now,
                    "loss": round(loss_now, 4),
                    "parallelism": args.parallelism,
                }
                if timer.steps_per_sec > 0:  # first drained window = compile
                    record["steps_per_sec"] = round(timer.steps_per_sec, 2)
                    record["tokens_per_sec"] = round(tokens_per_sec, 0)
                    if mfu is not None:
                        record["mfu"] = mfu
                print(json.dumps(record), flush=True)
        saved = (
            coordinated_maybe_save(
                ckpt,
                i_end,
                {"params": params, "opt_state": opt, "global_step": g},
                is_chief=chief,
                force=(i_end == args.training_steps),
                at_boundary=boundary,
            )
            if ckpt is not None
            else False
        )
        if boundary or saved:
            # Exclude boundary/save work from the next window; a mid-window
            # timed save drops the partial window (steps AND time).
            timer.mark(i_end)
        cur = nxt

    finally:
        prof.close()
        if writer is not None:
            writer.close()  # durable even if a step raised
    if jax.process_count() > 1 and args.parallelism in ("dp", "sp"):
        # Replicated-param modes: verify bitwise identity across processes
        # (the sharded modes' params are not fully addressable per process).
        from distributed_tensorflow_tpu.parallel import consistency

        consistency.check_cross_process_consistency(params)
    if args.output and not chief:
        args.output = ""  # chief-only export
    if args.output and jax.process_count() > 1 and args.parallelism not in ("dp", "sp"):
        print(
            f"skipping --output: {args.parallelism} params are sharded across "
            "processes (not addressable from the chief alone) — use "
            "--train_dir checkpoints, which save/restore cross-process "
            "shards natively",
            flush=True,
        )
        args.output = ""
    if args.output:
        from distributed_tensorflow_tpu.train.checkpoint import export_inference_bundle

        if args.parallelism == "fsdp":
            # Chunked (n_devices, chunk) padded leaves -> real model shapes,
            # so the bundle loads into a plain TransformerLM (generate.py).
            from distributed_tensorflow_tpu.parallel import fsdp

            out_params = fsdp.gather_fsdp_params(params, host)
        else:
            out_params = jax.device_get(params)
        export_inference_bundle(
            args.output,
            out_params,
            metadata={
                "model": "TransformerLM",
                "parallelism": args.parallelism,
                # Enough to rebuild TransformerConfig at load time —
                # generate.py prefers this over its shape flags.
                "config": {
                    "vocab_size": cfg.vocab_size,
                    "d_model": cfg.d_model,
                    "num_heads": cfg.num_heads,
                    "num_kv_heads": cfg.num_kv_heads or 0,
                    "attention_window": cfg.attention_window or 0,
                    "use_bias": int(cfg.use_bias),
                    # 0 = learned (pre-r5 bundles), 1 = rope.
                    "rope": int(cfg.position == "rope"),
                    "rope_theta": float(cfg.rope_theta),
                    "num_layers": cfg.num_layers,
                    "d_ff": cfg.d_ff,
                    "max_seq_len": cfg.max_seq_len,
                },
            },
        )
        print(f"exported {args.output}")
    # Fused chunks carry stacked (k,) losses; return the final step's.
    return float(np.asarray(jax.device_get(m["loss"])).reshape(-1)[-1])


if __name__ == "__main__":
    main()
