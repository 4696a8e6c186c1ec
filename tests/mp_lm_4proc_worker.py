"""Subprocess body for the 4-process two-axis LM integration test: a 2x2
(data x model) mesh across FOUR OS processes of one CPU device each — the
first mesh shape where cross-process *model*-axis collectives (tensor-
parallel psums between processes 0<->1 and 2<->3) compose with cross-process
data-axis gradient means AND cross-process sharded checkpoint saves.

The 2-process tests (mp_lm_worker.py) exercise each axis alone; this is the
multi-host composition the reference only gestured at with its 3-machine LAN
run (demo2/train.py:166-193).

Run as: python mp_lm_4proc_worker.py <task_index> <coordinator_port> <out_dir>
"""

import os
import sys


def main() -> None:
    task_index, port, out_dir = int(sys.argv[1]), sys.argv[2], sys.argv[3]

    # One local device per process: the 4 global devices reshape to a
    # ('data', 'model') = (2, 2) mesh in which BOTH axes cross process
    # boundaries (model pairs = processes {0,1} and {2,3}).
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=1"
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

    import numpy as np

    hosts = f"localhost:{port}," + ",".join(["localhost:0"] * 3)
    args = [
        "--worker_hosts", hosts,
        "--task_index", str(task_index),
        "--parallelism", "tp",
        "--model_parallel", "2",
        "--eval_step_interval", "4",
        "--seq_len", "32",
        "--batch_size", "8",  # global; data axis = 2 -> 4 sequences per row
        "--d_model", "32",
        "--num_layers", "2",
        "--d_ff", "64",
        "--train_dir", os.path.join(out_dir, "tp_ck"),
        "--save_secs", "0",
    ]
    # Phase 1: 4 steps, then a save whose model-axis param shards live on
    # DIFFERENT processes — Orbax must write each process's shards natively.
    loss1 = train_lm.main(args + ["--training_steps", "4"])
    assert np.isfinite(loss1), loss1
    # The save must actually exist as an Orbax step-4 dir (the train_dir
    # itself is created unconditionally by CheckpointManager.__init__, so
    # its existence proves nothing).
    step_dir = os.path.join(out_dir, "tp_ck", "4")
    assert os.path.isdir(step_dir), os.listdir(os.path.join(out_dir, "tp_ck"))
    # Phase 2: resume from the cross-process-sharded checkpoint to step 8.
    # The chief prints 'restored checkpoint at step 4' — asserted by the
    # parent test on this worker's captured stdout.
    loss2 = train_lm.main(args + ["--training_steps", "8"])
    assert np.isfinite(loss2), loss2
    assert os.path.isdir(os.path.join(out_dir, "tp_ck", "8"))

    print(f"LM4_WORKER_{task_index}_OK")


if __name__ == "__main__":
    main()
