"""Dataclass configs + argparse front-end.

Replaces the reference's flag system (argparse + module-global ``FLAGS`` +
``tf.app.run``, SURVEY C18). Flag names and defaults match the reference for
CLI parity:

* cluster flags — ``demo2/train.py:196-223`` (``--ps_hosts``, ``--worker_hosts``,
  ``--job_name``, ``--task_index``)
* retrain flags — ``retrain1/retrain.py:480-632`` and
  ``retrain2/retrain2.py:512-682`` (``--training_steps`` default differs:
  10000 single vs 2000 distributed)

Cluster semantics diverge deliberately: there are no parameter servers on TPU.
``--ps_hosts``/``--job_name=ps`` are accepted for CLI compatibility, but the
runtime is synchronous SPMD data-parallelism over a device mesh
(``--worker_hosts`` maps to JAX distributed processes; see
``parallel/distributed.py``).
"""

from __future__ import annotations

import argparse
import dataclasses
from dataclasses import dataclass, field
from typing import Any, Type, TypeVar

T = TypeVar("T")


def add_dataclass_flags(parser: argparse.ArgumentParser, cls: Type[Any]) -> None:
    """Auto-register one ``--flag`` per dataclass field (bools as 0/1-style
    store_true matching the reference's ``action='store_true'`` flags)."""
    for f in dataclasses.fields(cls):
        name = "--" + f.name
        default = f.default if f.default is not dataclasses.MISSING else f.default_factory()  # type: ignore[misc]
        help_text = f.metadata.get("help", "")
        if f.type in ("bool", bool):
            parser.add_argument(name, action="store_true", default=default, help=help_text)
        else:
            ftype = {"int": int, "float": float, "str": str}.get(str(f.type), type(default))
            parser.add_argument(name, type=ftype, default=default, help=help_text)


def from_args(cls: Type[T], args: argparse.Namespace) -> T:
    names = {f.name for f in dataclasses.fields(cls)}
    return cls(**{k: v for k, v in vars(args).items() if k in names})


def parse_flags(*classes: Type[Any], argv=None):
    """Parse known args into one instance per dataclass (mirrors the
    reference's ``parser.parse_known_args()`` tolerance of unknown flags,
    ``demo2/train.py:222``). Also the shared CLI bootstrap: enables the
    persistent XLA compilation cache (``utils/compile_cache.py``)."""
    from distributed_tensorflow_tpu.utils.compile_cache import (
        enable_compilation_cache,
    )

    enable_compilation_cache()
    parser = argparse.ArgumentParser()
    for cls in classes:
        add_dataclass_flags(parser, cls)
    ns, _ = parser.parse_known_args(argv)
    out = tuple(from_args(cls, ns) for cls in classes)
    return out[0] if len(out) == 1 else out


# ---------------------------------------------------------------------------
# Workload configs.
# ---------------------------------------------------------------------------


@dataclass
class MnistTrainConfig:
    """demo1/demo2 MNIST training (defaults from ``demo1/train.py:149-165``:
    10k steps, batch 100, Adam 1e-4, dropout keep_prob 0.7, eval every 100)."""

    data_dir: str = field(default="MNIST_data", metadata={"help": "idx .gz directory"})
    log_dir: str = field(default="./logs", metadata={"help": "summaries + autosave ckpts"})
    model_dir: str = field(default="./model", metadata={"help": "final checkpoint dir"})
    obs_dir: str = field(
        default="",
        metadata={"help": "observability output dir (flight-recorder crash "
                          "dumps + metrics JSONL + per-process fleet "
                          "snapshots, merged by the chief); empty disables "
                          "dumps"},
    )
    slo: str = field(
        default="",
        metadata={
            "help": "SLO rules evaluated at eval boundaries: 'default' "
            "(step time, data-wait fraction), 'off'/empty, and/or "
            "comma-separated 'metric[:agg]>thr[@sustain][#name]' specs"
        },
    )
    training_steps: int = 10000
    batch_size: int = 100
    model: str = field(
        default="cnn",
        metadata={"help": "classifier family: cnn (reference convnet) | vit"},
    )
    remat: bool = field(
        default=False,
        metadata={"help": "rematerialise transformer blocks (vit only)"},
    )
    learning_rate: float = 1e-4
    optimizer: str = field(
        default="adam",
        metadata={"help": "adam (reference demo parity) | adamw | sgd | momentum"},
    )
    lr_schedule: str = field(
        default="constant",
        metadata={"help": "constant (parity) | cosine | warmup_cosine | linear"},
    )
    warmup_steps: int = field(
        default=0, metadata={"help": "warmup_cosine ramp length"}
    )
    grad_clip_norm: float = field(
        default=0.0, metadata={"help": "global-norm gradient clip; 0 = off"}
    )
    dropout_rate: float = field(
        default=0.3, metadata={"help": "1 - keep_prob(0.7) from demo1/train.py:155"}
    )
    eval_step_interval: int = 100
    save_model_secs: int = field(
        default=600, metadata={"help": "Supervisor autosave parity, demo2/train.py:172"}
    )
    max_to_keep: int = field(
        default=5,
        metadata={"help": "checkpoints retained by the autosave manager"},
    )
    ckpt_async: int = field(
        default=1,
        metadata={
            "help": "zero-stall autosave: the device->host snapshot fetch "
            "and the disk write run on a background thread (forced/final "
            "saves still block until durable); 0 restores the synchronous "
            "fetch"
        },
    )
    snapshot_chunk_mb: int = field(
        default=64,
        metadata={
            "help": "chunk size of the double-buffered device->host "
            "snapshot copy (chunk i+1's transfer overlaps chunk i's "
            "materialization)"
        },
    )
    guard_nonfinite: int = field(
        default=1,
        metadata={
            "help": "skip optimizer updates whose global grad norm is "
            "non-finite (params/opt state untouched, step count advances, "
            "skipped_nonfinite metric emitted); 0 disables"
        },
    )
    rollback_bad_windows: int = field(
        default=2,
        metadata={
            "help": "after this many CONSECUTIVE eval windows containing "
            "non-finite (skipped) steps, roll back to the last good "
            "checkpoint; 0 disables rollback"
        },
    )
    max_rollbacks: int = field(
        default=3,
        metadata={
            "help": "give up (raise) after this many rollbacks in one run — "
            "a run that keeps diverging needs a human, not a loop"
        },
    )
    preempt_save: int = field(
        default=1,
        metadata={
            "help": "install SIGTERM/SIGINT handlers that trigger a "
            "coordinated emergency checkpoint at the next step boundary and "
            "exit cleanly; 0 disables"
        },
    )
    seed: int = 0
    synthetic_data: bool = field(
        default=False, metadata={"help": "generate deterministic synthetic MNIST if idx files absent"}
    )
    t10k_split: int = field(
        default=0,
        metadata={
            "help": "REAL-data mode for checkouts missing the 60k train-images "
            "blob: train on 10000-k of the genuine t10k digits, hold out k for "
            "eval (fixed split, independent of --seed); bundled copies in "
            "demo1/MNIST_data are used when --data_dir is left at its default"
        },
    )
    download_data: bool = field(
        default=False,
        metadata={
            "help": "fetch missing MNIST idx files first (the reference's "
            "auto-download; needs network egress)"
        },
    )
    profile_dir: str = field(
        default="",
        metadata={"help": "if set, write a jax.profiler (TensorBoard XPlane) trace here"},
    )
    profile_start_step: int = field(
        default=10, metadata={"help": "first traced step (after compile warmup)"}
    )
    profile_num_steps: int = field(default=5, metadata={"help": "traced step count"})
    steps_per_call: int = field(
        default=1,
        metadata={
            "help": "fuse k optimizer steps into one XLA dispatch (lax.scan) — "
            "amortizes per-step host overhead; semantics identical to k "
            "single steps"
        },
    )
    device_data: bool = field(
        default=False,
        metadata={
            "help": "keep the training set resident in HBM and sample batches "
            "on device inside the fused program (uniform per-shard sampling "
            "instead of epoch shuffling; fastest input path)"
        },
    )
    accum_steps: int = field(
        default=1,
        metadata={
            "help": "gradient accumulation: one optimizer step from k "
            "microbatch gradient means (effective batch k*batch_size whose "
            "activations never coexist in HBM); exclusive with "
            "steps_per_call>1 and device_data"
        },
    )
    export_stablehlo: bool = field(
        default=False,
        metadata={
            "help": "also export a frozen StableHLO inference program next to "
            "the final model bundle (weights baked in, runs without model code)"
        },
    )


@dataclass
class ClusterConfig:
    """PS/worker cluster flags (``demo2/train.py:196-223``), reinterpreted for
    SPMD: ``worker_hosts[0]`` is the coordinator, ``task_index`` the process
    index; ``ps_hosts`` is accepted-and-ignored (no parameter servers on TPU)."""

    ps_hosts: str = field(
        default="192.168.1.104:2221",
        metadata={"help": "accepted for CLI parity; unused (no PS on TPU)"},
    )
    # The reference defaulted to the author's two LAN IPs
    # (demo2/train.py:201,207) — with that default a bare invocation would
    # block waiting for a second process to join the coordination service.
    # Default here is single-process (all local devices); pass an explicit
    # multi-host list to go multi-process.
    worker_hosts: str = "localhost:12355"
    job_name: str = field(default="worker", metadata={"help": "'ps' exits with a notice"})
    task_index: int = 0
    initialization_timeout: int = field(
        default=120,
        metadata={
            "help": "seconds to wait for every worker to join the "
            "coordination service before failing loudly (a preempted or "
            "mis-addressed worker must not hang the job forever); 0 keeps "
            "the JAX default (300)"
        },
    )

    @property
    def worker_list(self) -> list[str]:
        return [h for h in self.worker_hosts.split(",") if h]

    @property
    def num_processes(self) -> int:
        return len(self.worker_list)

    @property
    def coordinator_address(self) -> str:
        return self.worker_list[0]

    @property
    def is_chief(self) -> bool:
        return self.task_index == 0


@dataclass
class RetrainConfig:
    """Transfer-learning flags, names/defaults from ``retrain1/retrain.py:480-632``."""

    image_dir: str = "./data"
    output_graph: str = field(
        default="./retrained_graph.msgpack",
        metadata={"help": "inference bundle (params); reference wrote a frozen .pb"},
    )
    output_labels: str = "./retrained_labels.txt"
    summaries_dir: str = "./retrain_logs"
    obs_dir: str = field(
        default="",
        metadata={"help": "observability output dir (per-process fleet "
                          "snapshots, merged by the chief); empty disables"},
    )
    training_steps: int = 10000
    learning_rate: float = 0.01
    optimizer: str = field(
        default="sgd",
        metadata={"help": "sgd (reference retrain parity) | adam | adamw | momentum"},
    )
    lr_schedule: str = field(
        default="constant",
        metadata={"help": "constant (parity) | cosine | warmup_cosine | linear"},
    )
    warmup_steps: int = 0
    grad_clip_norm: float = field(
        default=0.0, metadata={"help": "global-norm gradient clip; 0 = off"}
    )
    testing_percentage: int = 10
    validation_percentage: int = 10
    eval_step_interval: int = 10
    train_batch_size: int = 100
    test_batch_size: int = -1
    validation_batch_size: int = 100
    print_misclassified_test_images: bool = False
    model_dir: str = field(
        default="./inception_model",
        metadata={"help": "Inception-v3 weights dir (npz/msgpack); reference fetched a .pb"},
    )
    bottleneck_dir: str = "./bottleneck"
    final_tensor_name: str = "final_result"
    flip_left_right: bool = False
    random_crop: int = 0
    random_scale: int = 0
    random_brightness: int = 0
    seed: int = 0
    export_stablehlo: bool = field(
        default=False,
        metadata={
            "help": "also export a frozen StableHLO program next to "
            "--output_graph (closest analog of the reference's frozen .pb)"
        },
    )
    model_download_url: str = field(
        default="",
        metadata={
            "help": "when set and --model_dir has no weights, fetch+extract "
            "this .tgz first (the reference always downloaded "
            "inception-2015-12-05.tgz, retrain1/retrain.py:40-62; default off "
            "because this environment has no egress)"
        },
    )
    train_dir: str = field(
        default="",
        metadata={
            "help": "head-training checkpoint dir (Supervisor logdir parity, "
            "retrain2/retrain2.py:423-429: timed autosave + auto-restore); "
            "empty disables checkpointing (retrain1 reference behavior)"
        },
    )
    save_model_secs: int = field(
        default=600,
        metadata={"help": "autosave interval when --train_dir is set"},
    )
    max_to_keep: int = field(
        default=5,
        metadata={"help": "checkpoints retained when --train_dir is set"},
    )
    ckpt_async: int = field(
        default=1,
        metadata={
            "help": "zero-stall autosave (background snapshot + write) when "
            "--train_dir is set; 0 restores the synchronous fetch"
        },
    )
    snapshot_chunk_mb: int = field(
        default=64,
        metadata={
            "help": "chunk size of the double-buffered device->host "
            "snapshot copy"
        },
    )
    rollback_bad_windows: int = field(
        default=2,
        metadata={
            "help": "consecutive eval windows with non-finite (skipped) "
            "steps before rolling back to the last checkpoint (needs "
            "--train_dir); 0 disables"
        },
    )


@dataclass
class DistributedRetrainConfig(RetrainConfig):
    """retrain2 variant: ``--training_steps`` default 2000
    (``retrain2/retrain2.py:551``)."""

    training_steps: int = 2000


@dataclass
class ServeConfig:
    """Continuous-batching inference server (``serve/``, ``tools/serve_lm.py``).

    Beyond-reference: the source demos never serve. Defaults target the
    small-LM CPU/TPU demo path; production knobs are the slot count (batch
    capacity — more slots amortize weight reads until the KV read bound)
    and the admission pair ``max_queue_depth``/``request_timeout_s``."""

    host: str = field(default="127.0.0.1", metadata={"help": "bind address"})
    port: int = field(default=8000, metadata={"help": "bind port; 0 = ephemeral"})
    slots: int = field(
        default=4, metadata={"help": "concurrent request capacity (batch lanes)"}
    )
    serve_max_len: int = field(
        default=0,
        metadata={"help": "per-slot KV capacity; 0 = model max_seq_len"},
    )
    prefill_len: int = field(
        default=0,
        metadata={"help": "padded prompt capacity; 0 = serve_max_len // 2"},
    )
    max_queue_depth: int = field(
        default=64,
        metadata={"help": "queued requests beyond which submits shed (429)"},
    )
    request_timeout_s: float = field(
        default=60.0,
        metadata={"help": "HTTP handler wait before a 503 timeout answer"},
    )
    serve_log_dir: str = field(
        default="",
        metadata={"help": "if set, publish serving metrics to TB events here"},
    )
    obs_dir: str = field(
        default="",
        metadata={"help": "observability output dir (flight-recorder crash "
                          "dumps + metrics JSONL); empty disables dumps"},
    )
    metrics_interval_s: float = field(
        default=10.0, metadata={"help": "TB publish period"}
    )
    slo: str = field(
        default="default",
        metadata={
            "help": "SLO rules: 'default' (p99 TTFT, queue depth, "
            "post-warmup recompiles), 'off', and/or comma-separated "
            "'metric[:agg]>thr[@sustain][#name]' specs (obs/slo.py)"
        },
    )
    slo_interval_s: float = field(
        default=1.0,
        metadata={"help": "SLO monitor evaluation tick period"},
    )
    drain_deadline_s: float = field(
        default=10.0,
        metadata={
            "help": "SIGTERM grace: seconds the server keeps finishing "
            "accepted work (healthz 503, no new submits) before hard stop"
        },
    )
    lane_weights: str = field(
        default="8,4,1",
        metadata={
            "help": "admissions per scheduling cycle for priority lanes "
            "0 (interactive), 1 (normal), 2 (batch) under contention"
        },
    )
    page_size: int = field(
        default=-1,
        metadata={
            "help": "KV page size in tokens: -1 = auto (16 when it divides "
            "serve_max_len, else one whole-row page), >0 = explicit page "
            "size (a divisor of serve_max_len)"
        },
    )
    kv_pages: int = field(
        default=0,
        metadata={
            "help": "physical KV pages in the paged pool; 0 = worst case "
            "(slots * pages_per_slot + trash). Sizing below worst case "
            "oversubscribes: admission then gates on pages-free"
        },
    )
    prefix_cache: bool = field(
        default=True,
        metadata={
            "help": "adopt shared-prefix KV pages copy-free (paged layout "
            "only); shared-system-prompt traffic prefills only the tail"
        },
    )
    spec_k: int = field(
        default=0,
        metadata={
            "help": "speculative drafts per verify round (greedy requests, "
            "paged layout); 0 disables (default — opt in where the "
            "drafter fits the traffic; the verify program is one more "
            "warmup compile). Output is token-identical to plain "
            "decoding — this only changes latency"
        },
    )
    spec_branches: int = field(
        default=1,
        metadata={
            "help": "draft-tree branches per speculative verify round "
            "(requires spec_k > 0): 1 = linear drafts (default), N > 1 = "
            "a shared draft tree per slot (branch 0 the linear drafter, "
            "extras pooled from every active slot's history) verified in "
            "one widened forward under a tree-attention mask. Greedy "
            "output stays token-identical; sampled lanes stay lossless "
            "(multi-candidate rejection sampling)"
        },
    )
    kv_dtype: str = field(
        default="",
        metadata={
            "help": "live KV-cache page format: '' = model default, "
            "'bf16' = compute-dtype rows (explicit native), 'int8' = "
            "quantize-on-write int8 rows + per-row f32 scales with "
            "dequant fused on attend (~0.27x KV bytes/token vs f32; "
            "works under SlotEngine and ShardedSlotEngine — scale "
            "planes shard on the kv-head axis like the rows)"
        },
    )
    prefill_chunk_tokens: int = field(
        default=0,
        metadata={
            "help": "chunked-prefill budget per engine iteration: "
            "prompts whose tail exceeds this width prefill in "
            "chunks interleaved with decode steps, so prompts beyond "
            "prefill_len are admissible and long prefills never stall "
            "co-resident decodes. 0 = auto (prefill_len), -1 = off "
            "(prefill_len stays a hard prompt cap)"
        },
    )
    draft_model: str = field(
        default="",
        metadata={
            "help": "path to a tools/train_draft.py bundle: a small "
            "distilled draft LM replacing the n-gram drafter for "
            "spec_k rounds (greedy output stays token-identical — a "
            "better drafter only raises the accept rate). Empty = "
            "n-gram prompt-lookup drafting"
        },
    )
    draft_window: int = field(
        default=16,
        metadata={
            "help": "history suffix (tokens) the draft model conditions "
            "on per round; clamped to the draft bundle's max_seq_len "
            "minus spec_k"
        },
    )
    tp: int = field(
        default=1,
        metadata={
            "help": "tensor-parallel width of the serving mesh: 1 = one "
            "fully-replicated device (SlotEngine), N > 1 = one model "
            "partitioned over N devices behind the same slot API "
            "(ShardedSlotEngine; requires num_kv_heads % tp == 0 and "
            "d_model % tp == 0, validated before any jit)"
        },
    )
    weight_dtype: str = field(
        default="",
        metadata={
            "help": "weight-only quantization for serving: '' = the "
            "bundle's native weights, 'int8' = symmetric per-channel "
            "(scales factor out of the matmul exactly), 'int4' = "
            "group-wise along the input axis (needs quant_group_size; "
            "dequant in-register). Embeddings/norms/lm_head stay "
            "high-precision (models/quant.py)"
        },
    )
    quant_group_size: int = field(
        default=0,
        metadata={
            "help": "int4 scale-group size along the matmul input axis "
            "(even, dividing d_model and d_ff — e.g. 32/64/128); must be "
            "0 for '' / 'int8'"
        },
    )
    role: str = field(
        default="mixed",
        metadata={
            "help": "disaggregated-tier role: 'prefill' (runs prompt "
            "prefill + first token, then hands the slot's KV pages to a "
            "decode peer), 'decode' (imports handed-off slots via POST "
            "/handoff), 'mixed' (classic single-tier replica; default)"
        },
    )
    handoff_peers: str = field(
        default="",
        metadata={
            "help": "comma-separated decode-tier base URLs a prefill "
            "replica pushes handoffs to (also settable at runtime via "
            "POST /admin/handoff_peers)"
        },
    )
    handoff_wire: int = field(
        default=2,
        metadata={
            "help": "handoff wire format a prefill replica SENDS: 2 = "
            "chunked pipelined DTFH2 stream (default; encode overlaps "
            "send, per-chunk CRC, optional zlib), 1 = monolithic DTFH1 "
            "bundle. Receivers always accept both"
        },
    )
    handoff_chunk_pages: int = field(
        default=4,
        metadata={
            "help": "KV pages per DTFH2 chunk frame — the pipelining "
            "grain: smaller = better encode/send overlap + finer "
            "receiver scatters, larger = less framing overhead"
        },
    )
    handoff_compress: bool = field(
        default=True,
        metadata={
            "help": "zlib-compress DTFH2 chunk payloads when the "
            "measured ratio clears the skip-if-incompressible guard "
            "(stdlib zlib level 1; incompressible chunks ship raw)"
        },
    )

    @property
    def handoff_peer_list(self) -> tuple:
        return tuple(u.strip() for u in self.handoff_peers.split(",")
                     if u.strip())

    @property
    def lane_weight_tuple(self) -> tuple:
        return tuple(int(w) for w in self.lane_weights.split(","))

    @property
    def engine_page_size(self) -> int | None:
        """Resolve the ``page_size`` flag for SlotEngine: None = engine
        auto-pick, else the explicit value (the engine refuses 0)."""
        return None if self.page_size < 0 else self.page_size

    def validate_mesh(self, model_cfg) -> None:
        """Fail fast — at config-build time, with an actionable message —
        on a ``tp`` the model's shapes cannot shard, instead of a shape
        error deep inside jit. No-op for ``tp <= 1``."""
        if self.tp > 1:
            validate_tp_mesh(model_cfg, self.tp)

    def validate_quant(self, model_cfg) -> None:
        """Fail fast on a weight-quantization config the model's shapes
        cannot satisfy (group-size divisibility, int4-requires-grouping,
        int4-under-tp group alignment) — the ``validate_mesh`` discipline
        for the ``weight_dtype``/``quant_group_size`` pair. No-op when
        quantization is off."""
        if self.weight_dtype or self.quant_group_size:
            from distributed_tensorflow_tpu.models.quant import (
                validate_weight_quant,
            )

            validate_weight_quant(
                self.weight_dtype or None, self.quant_group_size,
                int(model_cfg.d_model), int(model_cfg.d_ff),
                tp=max(1, int(self.tp)),
            )

    def validate_kv(self) -> None:
        """Fail fast on a KV-format / speculation combination the engine
        would reject anyway — at config-build time, with the flag names in
        the message."""
        if self.kv_dtype not in ("", "bf16", "int8"):
            raise ValueError(
                f"kv_dtype must be '', 'bf16' or 'int8', got "
                f"{self.kv_dtype!r}"
            )
        if self.spec_branches < 1:
            raise ValueError(
                f"spec_branches must be >= 1, got {self.spec_branches}"
            )
        if self.spec_branches > 1 and not self.spec_k:
            raise ValueError(
                "spec_branches > 1 requires spec_k > 0 (tree speculation "
                "widens the verify block; there is nothing to widen "
                "without drafts)"
            )

    @property
    def engine_kv_cache_dtype(self):
        """Resolve ``kv_dtype`` to ``TransformerConfig.kv_cache_dtype``:
        ``''`` keeps the model bundle's own setting (no override),
        ``'bf16'`` forces native compute-dtype rows (``None``), ``'int8'``
        forces quantize-on-write int8 pages. Returns the sentinel string
        ``'keep'`` for no-override so callers can distinguish it from an
        explicit ``None``."""
        if not self.kv_dtype:
            return "keep"
        return "int8" if self.kv_dtype == "int8" else None


def validate_tp_mesh(model_cfg, tp: int) -> None:
    """Shared tp-divisibility check (ServeConfig AND ShardedSlotEngine call
    this). ``model_cfg`` needs ``kv_heads`` and ``d_model`` attributes."""
    tp = int(tp)
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    kv = int(model_cfg.kv_heads)
    if kv % tp:
        divisors = [d for d in range(1, kv + 1) if kv % d == 0]
        raise ValueError(
            f"tp={tp} does not divide num_kv_heads={kv}: GQA-under-TP "
            "shards whole query groups along the kv-head axis (KV pages "
            "included), so num_kv_heads % tp must be 0. Pick tp from "
            f"{divisors} or change the model's num_kv_heads."
        )
    dm = int(model_cfg.d_model)
    if dm % tp:
        raise ValueError(
            f"tp={tp} does not divide d_model={dm}: the column/row-"
            "parallel kernels split the model dim evenly across the "
            "'model' mesh axis. Pick a tp that divides d_model."
        )


@dataclass
class FleetConfig:
    """Router tier over N replicas (``serve/fleet/``,
    ``tools/serve_fleet.py``). Flag names carry a ``router_``/``fleet_``
    prefix so they compose with :class:`ServeConfig` in one parser (the
    launcher forwards the serve flags to every replica)."""

    router_host: str = field(default="127.0.0.1", metadata={"help": "router bind address"})
    router_port: int = field(
        default=8100, metadata={"help": "router bind port; 0 = ephemeral"}
    )
    num_replicas: int = field(
        default=2, metadata={"help": "local replicas the launcher spawns"}
    )
    probe_interval_s: float = field(
        default=0.25, metadata={"help": "health-check period per replica"}
    )
    up_after: int = field(
        default=2,
        metadata={"help": "consecutive healthy probes before down->up"},
    )
    down_after: int = field(
        default=2,
        metadata={"help": "consecutive failed probes before up->down"},
    )
    max_attempts: int = field(
        default=3,
        metadata={"help": "dispatch tries per request (1 + failovers)"},
    )
    fleet_slo: str = field(
        default="default",
        metadata={
            "help": "router SLO rules: 'default' (fleet_pressure, up-replica "
            "floor, routed p99 TTFT), 'off', and/or compact specs"
        },
    )
    fleet_slo_interval_s: float = field(
        default=1.0, metadata={"help": "router SLO evaluation tick period"}
    )
    # Disaggregated tiers: when either count is > 0 the launcher spawns
    # role-tagged replicas instead of num_replicas mixed ones and pushes
    # the decode tier's URLs to every prefill replica's handoff outbox.
    prefill_replicas: int = field(
        default=0,
        metadata={"help": "prefill-tier replicas (0 = no disaggregation; "
                  "with decode_replicas, replaces num_replicas)"},
    )
    decode_replicas: int = field(
        default=0,
        metadata={"help": "decode-tier replicas receiving KV-page "
                  "handoffs (0 = no disaggregation)"},
    )
    # Elastic supervision (tools/serve_fleet.py --supervise).
    supervise: bool = field(
        default=False,
        metadata={"help": "run the FleetSupervisor: replica processes "
                  "become supervised + autoscaled instead of a static "
                  "launch list (replacements re-announce on stdout)"},
    )
    min_replicas: int = field(
        default=1,
        metadata={"help": "autoscaler floor (supervised mode)"},
    )
    max_replicas: int = field(
        default=4,
        metadata={"help": "autoscaler ceiling (supervised mode)"},
    )
    scale_high_watermark: float = field(
        default=0.85,
        metadata={"help": "fleet_pressure above this (sustained) scales "
                  "up"},
    )
    scale_low_watermark: float = field(
        default=0.25,
        metadata={"help": "fleet_pressure below this (sustained) scales "
                  "down"},
    )
    scale_up_sustain_s: float = field(
        default=1.0,
        metadata={"help": "seconds pressure must hold above the high "
                  "watermark before a scale-up"},
    )
    scale_down_sustain_s: float = field(
        default=10.0,
        metadata={"help": "seconds pressure must hold below the low "
                  "watermark before a scale-down"},
    )
    scale_cooldown_s: float = field(
        default=5.0,
        metadata={"help": "seconds after any scaling decision during "
                  "which no further decision is taken (flap control)"},
    )
    supervisor_tick_s: float = field(
        default=0.5, metadata={"help": "policy loop evaluation period"}
    )
    balance_tiers: bool = field(
        default=False,
        metadata={"help": "supervised disaggregated fleets only: each "
                  "scaling decision picks WHICH tier to grow/shrink from "
                  "the prefill admission-load vs decode page-occupancy "
                  "split instead of always scaling the fixed role"},
    )
    drain_grace_s: float = field(
        default=15.0,
        metadata={"help": "scale-down drain window: SIGTERM -> graceful "
                  "drain -> SIGKILL after this many seconds"},
    )
    # Chaos defenses (PR 16): hedging, circuit breakers, read watchdog.
    hedge_after_s: float = field(
        default=-1.0,
        metadata={"help": "tail-latency hedge delay for buffered "
                  "dispatches: <0 = disabled, 0 = adaptive (p95 of the "
                  "router's recent latency window), >0 = fixed seconds"},
    )
    read_timeout_s: float = field(
        default=30.0,
        metadata={"help": "per-attempt upstream read watchdog: a replica "
                  "that accepts the connection but never answers is "
                  "treated as a dispatch failure (feeds its breaker) "
                  "instead of holding the request forever"},
    )
    breaker_window: int = field(
        default=8,
        metadata={"help": "dispatch outcomes per replica scored for the "
                  "circuit breaker (sliding window)"},
    )
    breaker_fail_threshold: float = field(
        default=0.5,
        metadata={"help": "failure fraction over the window that trips a "
                  "replica's breaker open"},
    )
    breaker_min_samples: int = field(
        default=4,
        metadata={"help": "minimum outcomes in the window before the "
                  "breaker may trip (single blips never open it)"},
    )
    breaker_open_s: float = field(
        default=2.0,
        metadata={"help": "seconds a tripped breaker stays open before "
                  "admitting one half-open trial dispatch"},
    )
    router_obs_dir: str = field(
        default="",
        metadata={"help": "router-side observability dir: breaker-open "
                  "flight-recorder dumps + the end-of-run "
                  "fleet_storm_summary.json land here (distinct from "
                  "--obs_dir, which is forwarded to every replica)"},
    )


@dataclass
class DeployConfig:
    """Checkpoint hot-swap + canary + variants (``serve/deploy/``).

    All off by default: with ``watch_dir`` empty no watcher starts and
    the serving stack is byte-identical to the pre-deploy build. Flags
    carry a ``deploy_``/``canary_`` prefix so they compose with
    :class:`ServeConfig` / :class:`FleetConfig` in one parser."""

    watch_dir: str = field(
        default="",
        metadata={"help": "checkpoint dir to poll for committed steps; "
                  "empty = hot-swap disabled"},
    )
    watch_interval_s: float = field(
        default=0.25, metadata={"help": "watcher poll period"}
    )
    deploy_params_key: str = field(
        default="auto",
        metadata={"help": "subtree of the checkpoint to serve: 'auto' "
                  "(tree['params'] when present), '' (whole tree), or a "
                  "'/'-separated path"},
    )
    deploy_variant: str = field(
        default="",
        metadata={"help": "variant new checkpoints deploy into; empty = "
                  "the live/default variant (in-place hot swap)"},
    )
    canary_percent: float = field(
        default=0.0,
        metadata={"help": "percent of client_id hash lanes (0-100) routed "
                  "to the canary variant once it exists"},
    )
    canary_variant: str = field(
        default="canary",
        metadata={"help": "name of the canary variant in the table"},
    )
    canary_rows: int = field(
        default=4,
        metadata={"help": "held-out canary eval batch rows"},
    )
    canary_len: int = field(
        default=16,
        metadata={"help": "held-out canary eval sequence length"},
    )
    canary_probes: int = field(
        default=2,
        metadata={"help": "probe prompts greedily continued pre-flip"},
    )
    max_loss_ratio: float = field(
        default=1.5,
        metadata={"help": "candidate/live canary eval-loss ratio above "
                  "which the swap rolls back"},
    )

    def validate(self) -> None:
        if not 0.0 <= self.canary_percent <= 100.0:
            raise ValueError(
                f"canary_percent must be in [0, 100], got "
                f"{self.canary_percent}"
            )
        if self.max_loss_ratio <= 0:
            raise ValueError(
                f"max_loss_ratio must be > 0, got {self.max_loss_ratio}"
            )
        if self.watch_interval_s <= 0:
            raise ValueError(
                f"watch_interval_s must be > 0, got {self.watch_interval_s}"
            )
        if self.canary_rows < 1 or self.canary_len < 2:
            raise ValueError(
                "canary batch needs >= 1 row and length >= 2 (next-token "
                f"loss), got rows={self.canary_rows} len={self.canary_len}"
            )

    @property
    def enabled(self) -> bool:
        return bool(self.watch_dir)
