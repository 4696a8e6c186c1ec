"""Inference-serving subsystem: continuous (iteration-level) batching.

The training half of the framework got its robustness story in PR 1/2; this
package opens the OTHER half of the ROADMAP north star ("serves heavy
traffic") the same way: a TF-free, jit-stable engine that decodes a
fixed-capacity slot batch — requests join and leave at TOKEN granularity
(Orca-style), the per-slot KV cache lives in a pooled, donated buffer
(vLLM-slot-style, built on ``models/decoding.init_cache`` incl. int8-KV),
and shapes never change after warmup so nothing ever recompiles under load.

Layers (each importable on its own):
  * ``kv_pool``   — slot-pooled KV buffers: allocate/free/adopt in place
  * ``engine``    — the jitted prefill + decode-step programs
  * ``scheduler`` — FCFS queue, admission control, typed load-shed
  * ``metrics``   — TTFT / per-token-latency / occupancy histograms (+ TB)
  * ``server``    — stdlib-only ``http.server`` JSON endpoint

See ``docs/DESIGN.md`` §11 for the contracts.
"""

from distributed_tensorflow_tpu.serve.engine import (
    ShardedSlotEngine,
    SlotEngine,
)
from distributed_tensorflow_tpu.serve.metrics import Histogram, ServingMetrics
from distributed_tensorflow_tpu.serve.scheduler import (
    Completion,
    Rejection,
    Request,
    Scheduler,
)

__all__ = [
    "SlotEngine",
    "ShardedSlotEngine",
    "Histogram",
    "ServingMetrics",
    "Request",
    "Completion",
    "Rejection",
    "Scheduler",
]
