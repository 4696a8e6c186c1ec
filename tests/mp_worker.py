"""Subprocess body for the 2-process distributed integration test
(``test_multiprocess.py``). Exercises the real multi-process path the demo2
CLI uses: ``initialize_from_cluster`` (jax.distributed over the reference's
worker_hosts/task_index flags) → global mesh over all processes' devices →
``psum`` across the process boundary → chief-only side effects → barrier.

Run as: python mp_worker.py <task_index> <coordinator_port> <out_dir>
"""

import os
import sys


def main() -> None:
    task_index, port, out_dir = int(sys.argv[1]), sys.argv[2], sys.argv[3]

    # 2 virtual CPU devices per process -> 4 global devices over 2 processes.
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=2"
    ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp
    import numpy as np

    from distributed_tensorflow_tpu.config import ClusterConfig
    from distributed_tensorflow_tpu.parallel import distributed as D

    cluster = ClusterConfig(
        worker_hosts=f"localhost:{port},localhost:0",  # second entry only sets count
        job_name="worker",
        task_index=task_index,
    )
    # num_processes comes from the worker list length (2).
    assert cluster.num_processes == 2
    assert D.initialize_from_cluster(cluster)
    assert jax.process_count() == 2
    assert jax.local_device_count() == 2
    assert jax.device_count() == 4
    assert D.is_chief() == (task_index == 0)

    # Cross-process collective through the demo2 machinery: a global mesh over
    # all 4 devices; each shard contributes (process_index+1); the psum must
    # see every shard on both processes.
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()).reshape(4), ("data",))
    arr = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("data")), np.full((2, 1), float(jax.process_index() + 1))
    )

    def tot(x):
        return jax.lax.psum(jnp.sum(x), "data")

    total = jax.jit(
        jax.shard_map(tot, mesh=mesh, in_specs=P("data"), out_specs=P(), check_vma=False)
    )(arr)
    # shards: proc0 holds two rows of 1.0, proc1 two rows of 2.0 -> sum 6.
    assert float(jax.device_get(total)) == 6.0, float(jax.device_get(total))

    # Chief-only side effect + barrier (Supervisor init-order parity).
    if D.is_chief():
        with open(os.path.join(out_dir, "chief.txt"), "w") as fh:
            fh.write("ok")
    D.barrier("test_done")
    # After the barrier every process must see the chief's file.
    assert os.path.exists(os.path.join(out_dir, "chief.txt"))
    print(f"WORKER_{task_index}_OK")


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
    main()
