"""Subprocess body for the end-to-end 2-process demo2 training test: runs the
ACTUAL demo2 CLI main() — cluster flags → jax.distributed → global mesh →
SPMD training with per-worker independent sampling → cross-process param
consistency check → chief-only export.

Run as: python mp_demo2_worker.py <task_index> <coordinator_port> <log_dir>
"""

import os
import sys


def main() -> None:
    task_index, port, log_dir = int(sys.argv[1]), sys.argv[2], sys.argv[3]

    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=2"
    ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "demo2_train",
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "demo2", "train.py"),
    )
    demo2 = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(demo2)

    stats = demo2.main(
        [
            "--worker_hosts", f"localhost:{port},localhost:0",
            "--task_index", str(task_index),
            "--training_steps", "12",
            "--eval_step_interval", "6",
            "--batch_size", "8",
            "--synthetic_data", "1",
            "--steps_per_call", "3",  # fused path must also work cross-process
            "--log_dir", log_dir,
        ]
    )
    assert stats is not None and stats["steps"] == 12, stats
    # demo2.main already ran check_cross_process_consistency (raises on drift).
    if task_index == 0:
        assert os.path.exists(os.path.join(log_dir, "model.msgpack"))
    print(f"DEMO2_WORKER_{task_index}_OK")


if __name__ == "__main__":
    main()
