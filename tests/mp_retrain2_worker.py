"""Subprocess body for the 2-process distributed retrain test (reference C16):
process group from cluster flags → stride-sharded bottleneck caching with a
barrier → synchronous SPMD head training over the global mesh → chief-only
export. Uses the fast color-feature extractor (the Inception trunk is
exercised elsewhere); everything else is the real retrain2 machinery.

Run as: python mp_retrain2_worker.py <task_index> <port> <work_dir>
"""

import os
import sys


def main() -> None:
    task_index, port, work = int(sys.argv[1]), sys.argv[2], sys.argv[3]

    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=2"
    ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
    from distributed_tensorflow_tpu.config import ClusterConfig, DistributedRetrainConfig
    from distributed_tensorflow_tpu.parallel import distributed as D
    from distributed_tensorflow_tpu.parallel.mesh import make_mesh
    from distributed_tensorflow_tpu.train.retrain_loop import RetrainTrainer
    from tests.test_retrain import ColorExtractor

    cluster = ClusterConfig(
        worker_hosts=f"localhost:{port},localhost:0",
        job_name="worker",
        task_index=task_index,
    )
    assert D.initialize_from_cluster(cluster)
    cfg = DistributedRetrainConfig(
        image_dir=os.path.join(work, "data"),
        bottleneck_dir=os.path.join(work, "bn"),
        summaries_dir=os.path.join(work, "sum"),
        output_graph=os.path.join(work, "graph.msgpack"),
        output_labels=os.path.join(work, "labels.txt"),
        training_steps=20,
        learning_rate=0.5,
        train_batch_size=16,
        validation_batch_size=8,
        eval_step_interval=10,
        testing_percentage=20,
        validation_percentage=20,
        seed=0,
        train_dir=os.path.join(work, "ckpt"),  # coordinated Supervisor-parity saves
    )
    trainer = RetrainTrainer(
        cfg,
        mesh=make_mesh(),
        extractor=ColorExtractor(),
        is_chief=D.is_chief(),
        process_index=jax.process_index(),
        process_count=jax.process_count(),
    )
    stats = trainer.train()
    assert stats["steps"] == 20, stats
    assert stats["test_accuracy"] >= 0.5, stats  # separable colors
    if D.is_chief():
        assert os.path.exists(cfg.output_graph)
        assert os.path.exists(cfg.output_labels)
    D.barrier("retrain2_done")
    print(f"RETRAIN2_WORKER_{task_index}_OK test_acc={stats['test_accuracy']:.2f}")


if __name__ == "__main__":
    main()
