"""Subprocess body for the 2-process LM-training integration test: runs the
ACTUAL tools/train_lm.py main() with reference-style cluster flags —
jax.distributed group → global mesh → SPMD LM training with identical
global batches sliced per process → cross-process param consistency check →
chief-only export.

Run as: python mp_lm_worker.py <task_index> <coordinator_port> <out_dir>
"""

import os
import sys


def main() -> None:
    task_index, port, out_dir = int(sys.argv[1]), sys.argv[2], sys.argv[3]

    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=2"
    ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")

    repo = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    sys.path.insert(0, repo)
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "train_lm", os.path.join(repo, "tools", "train_lm.py")
    )
    train_lm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(train_lm)

    bundle = os.path.join(out_dir, "lm.msgpack")
    loss = train_lm.main(
        [
            "--worker_hosts", f"localhost:{port},localhost:0",
            "--task_index", str(task_index),
            "--parallelism", "dp",
            "--training_steps", "8",
            "--eval_step_interval", "4",
            "--seq_len", "32",
            "--batch_size", "8",  # global; 4 global devices -> 2 per device
            "--d_model", "32",
            "--num_layers", "2",
            "--d_ff", "64",
            "--output", bundle,
        ]
    )
    import numpy as np

    assert np.isfinite(loss), loss
    # main() ran check_cross_process_consistency (raises on divergence) and
    # the chief exported the bundle.
    if task_index == 0:
        assert os.path.exists(bundle)

    # Phase 2: fsdp with --train_dir — params/opt sharded ACROSS the two
    # processes; the save must write cross-process shards natively and the
    # resumed run must restore them (4 steps, save, resume to 8).
    fsdp_args = [
        "--worker_hosts", f"localhost:{port},localhost:0",
        "--task_index", str(task_index),
        "--parallelism", "fsdp",
        "--eval_step_interval", "4",
        "--seq_len", "32",
        "--batch_size", "8",
        "--d_model", "32",
        "--num_layers", "2",
        "--d_ff", "64",
        "--train_dir", os.path.join(out_dir, "fsdp_ck"),
        "--save_secs", "0",
    ]
    loss1 = train_lm.main(fsdp_args + ["--training_steps", "4"])
    assert np.isfinite(loss1), loss1
    loss2 = train_lm.main(fsdp_args + ["--training_steps", "8"])
    assert np.isfinite(loss2), loss2

    # Phase 3: sp_tp with the 'pipe' (sequence) axis spanning BOTH processes
    # and a size-1 data axis — the placement regression case (a batch-dim
    # slice-by-process would feed devices garbage and NaN from step 1).
    loss3 = train_lm.main(
        [
            "--worker_hosts", f"localhost:{port},localhost:0",
            "--task_index", str(task_index),
            "--parallelism", "sp_tp",
            "--pipeline_parallel", "4",
            "--model_parallel", "1",
            "--training_steps", "4",
            "--eval_step_interval", "4",
            "--seq_len", "32",
            "--batch_size", "4",
            "--d_model", "32",
            "--num_layers", "2",
            "--d_ff", "64",
        ]
    )
    assert np.isfinite(loss3), loss3
    print(f"LM_WORKER_{task_index}_OK")


if __name__ == "__main__":
    main()
