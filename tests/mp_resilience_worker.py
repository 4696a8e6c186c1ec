"""Subprocess body for the kill-and-resume multiprocess resilience test
(``test_resilience.py::test_kill_and_resume_two_process``) — the same
2-process cluster bring-up as ``mp_worker.py`` (cluster flags →
jax.distributed → global mesh), then real MNIST training through
``MnistTrainer`` so the coordinated preemption path (allgather agreement at
eval boundaries → collective emergency save → clean exit) and the restart
resume path are exercised across actual OS processes.

Run as: python mp_resilience_worker.py <task_index> <coordinator_port> <log_dir>

Env:
  DTT_FAULT="preempt:step=N"   arm a synthetic preemption (test sets it on
                               worker 0 only — worker 1 must stop anyway)
  DTT_RESIL_EXPECT_STEPS       the step count this run must stop at
"""

import os
import sys


def main() -> None:
    task_index, port, log_dir = int(sys.argv[1]), sys.argv[2], sys.argv[3]

    # 2 virtual CPU devices per process -> 4 global devices over 2 processes.
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=2"
    ).strip()
    os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
    import jax

    jax.config.update("jax_platforms", "cpu")

    from distributed_tensorflow_tpu.config import ClusterConfig, MnistTrainConfig
    from distributed_tensorflow_tpu.data.mnist import read_data_sets
    from distributed_tensorflow_tpu.parallel import distributed as D
    from distributed_tensorflow_tpu.parallel.consistency import (
        check_cross_process_consistency,
    )
    from distributed_tensorflow_tpu.parallel.mesh import make_mesh
    from distributed_tensorflow_tpu.train.loop import MnistTrainer

    cluster = ClusterConfig(
        worker_hosts=f"localhost:{port},localhost:0",  # second entry only sets count
        job_name="worker",
        task_index=task_index,
    )
    assert D.initialize_from_cluster(cluster)
    assert jax.process_count() == 2

    expect = int(os.environ.get("DTT_RESIL_EXPECT_STEPS", "12"))
    cfg = MnistTrainConfig(
        data_dir="unused",
        log_dir=log_dir,
        model_dir=os.path.join(log_dir, "model"),
        training_steps=12,
        batch_size=8,
        eval_step_interval=4,
        learning_rate=1e-3,
        synthetic_data=True,
        save_model_secs=3600,  # only boundary/emergency/final saves
        seed=0,
    )
    datasets = read_data_sets(
        "unused", one_hot=True, seed=0, synthetic=True,
        num_synthetic_train=256, num_synthetic_test=64,
    )
    trainer = MnistTrainer(
        cfg, mesh=make_mesh(), datasets=datasets, is_chief=D.is_chief()
    )
    stats = trainer.train()
    assert stats["steps"] == expect, (stats, expect)
    # Both processes must exit with bitwise-identical params — a unilateral
    # stop would leave one process a step ahead.
    check_cross_process_consistency(trainer.params)
    print(f"RESIL_WORKER_{task_index}_OK steps={stats['steps']}")


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
    main()
