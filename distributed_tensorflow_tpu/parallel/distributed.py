"""Multi-process coordination.

Replaces the reference's ``tf.train.ClusterSpec`` / ``tf.train.Server`` gRPC
runtime and ``Supervisor`` chief election (``demo2/train.py:11-29,166-176``):

  * process group        → ``jax.distributed.initialize`` (coordinator =
    first worker host, parity with the reference's chief = task_index 0)
  * parameter servers    → none. Parameters live replicated/sharded in HBM;
    gradient sync is an XLA collective over ICI/DCN. A ``--job_name=ps``
    launch is accepted and exits with an explanation (the process simply has
    no role to play — ps hosts in the reference block in ``server.join()``
    forever, ``demo2/train.py:23-24``).
  * chief responsibilities (init/ckpt/summaries) → ``jax.process_index()==0``.
"""

from __future__ import annotations

import jax

from distributed_tensorflow_tpu.config import ClusterConfig
from distributed_tensorflow_tpu.utils.logging import get_logger

log = get_logger(__name__)


def initialize_from_cluster(cluster: ClusterConfig) -> bool:
    """Initialize the JAX process group from reference-style cluster flags.

    Returns False (after logging) for ``--job_name=ps`` — the caller should
    exit: there are no parameter servers in a synchronous SPMD runtime.

    ``cluster.initialization_timeout`` bounds the wait for stragglers: a
    worker that never joins (preempted before start, wrong address) makes
    ``jax.distributed.initialize`` raise after that many seconds instead of
    the job hanging forever — fail loudly, then let the scheduler retry."""
    if cluster.job_name == "ps":
        log.info(
            "job_name=ps accepted for CLI parity but parameter servers do not "
            "exist on TPU: parameters are device-resident and gradients are "
            "all-reduced over ICI. This process has nothing to do; exiting."
        )
        return False
    if cluster.num_processes > 1:
        if jax.distributed.is_initialized():
            # Already in a group (repeated main() calls, e.g. a resume in
            # the same process) — initialize would raise. NOTE: must not
            # probe via jax.process_count(): that itself initialises the
            # XLA backend, which forbids a later initialize().
            return True
        kwargs = {}
        timeout = int(getattr(cluster, "initialization_timeout", 0) or 0)
        if timeout > 0:
            kwargs["initialization_timeout"] = timeout
        jax.distributed.initialize(
            coordinator_address=cluster.coordinator_address,
            num_processes=cluster.num_processes,
            process_id=cluster.task_index,
            **kwargs,
        )
        log.info(
            "joined process group: process %d/%d, %d local / %d global devices",
            jax.process_index(),
            jax.process_count(),
            jax.local_device_count(),
            jax.device_count(),
        )
    return True


def is_chief() -> bool:
    return jax.process_index() == 0


def barrier(name: str = "barrier") -> None:
    """Cross-process sync point (Supervisor's wait-for-chief-init analog)."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(name)
