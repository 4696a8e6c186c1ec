"""Subprocess body for the 2-process ASYNC-autosave integration test
(``test_multiprocess.py::test_two_process_async_autosave_deferred_finalize``):
the same cluster bring-up as ``mp_worker.py``, then MNIST training with
``save_model_secs=0`` so the timed gate fires at every eval boundary — each
of those saves is issued NON-blocking (``wait=False``): per-process sharded
shard writes on the background snapshot thread, with the collective COMMIT
deferred to the next boundary's ``finalize_pending`` on the main thread.
This is exactly the interleaving (async save vs ``broadcast_one_to_all``)
that used to deadlock and forced multi-process saves synchronous; the run
must complete, commit the mid-run step, and a same-process relaunch must
restore from the final one.

Run as: python mp_async_ckpt_worker.py <task_index> <coordinator_port> <log_dir>
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

    cluster = ClusterConfig(
        worker_hosts=f"localhost:{port},localhost:0",  # second entry only sets count
        job_name="worker",
        task_index=task_index,
    )
    assert D.initialize_from_cluster(cluster)
    assert jax.process_count() == 2

    def cfg(steps: int) -> MnistTrainConfig:
        return MnistTrainConfig(
            data_dir="unused",
            log_dir=log_dir,
            model_dir=os.path.join(log_dir, "model"),
            training_steps=steps,
            batch_size=8,
            eval_step_interval=4,
            learning_rate=1e-3,
            synthetic_data=True,
            save_model_secs=0,  # the gate fires at EVERY boundary: async saves
            seed=0,
        )

    datasets = read_data_sets(
        "unused", one_hot=True, seed=0, synthetic=True,
        num_synthetic_train=256, num_synthetic_test=64,
    )
    from distributed_tensorflow_tpu.train.loop import MnistTrainer

    # Phase 1: the boundary-4 save is issued async (non-wait) and committed
    # by the deferred finalize at boundary 8; the final step-8 save is forced
    # (synchronous + committed). Both must exist, and nothing may deadlock.
    t1 = MnistTrainer(cfg(8), mesh=make_mesh(), datasets=datasets, is_chief=D.is_chief())
    stats = t1.train()
    assert stats["steps"] == 8, stats
    committed = t1.ckpt.all_steps()
    assert {4, 8} <= set(committed), committed
    assert t1.ckpt.latest_step() == 8
    check_cross_process_consistency(t1.params)

    # Phase 2: a relaunch (same process, repeated main-style construction)
    # restores the per-process sharded step-8 save and runs to 12 — the
    # MnistTrainer __init__ logs 'restored checkpoint at step 8', asserted
    # by the parent test on this worker's captured output.
    t2 = MnistTrainer(cfg(12), mesh=make_mesh(), datasets=datasets, is_chief=D.is_chief())
    assert int(jax.device_get(t2.global_step)) == 8
    stats2 = t2.train()
    assert stats2["steps"] == 12, stats2
    check_cross_process_consistency(t2.params)
    print(f"ASYNC_CKPT_WORKER_{task_index}_OK steps={stats2['steps']}")


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
    main()
