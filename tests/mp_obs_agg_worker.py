"""Subprocess body for the 2-process fleet-aggregation test: run the real
demo2 training CLI with a shared ``--obs_dir`` so every process drops
``fleet_p<i>.json`` snapshots through the live train-loop wiring, then add
process-distinct histogram traffic, snapshot again, and let the chief merge
the fleet: counters must SUM across processes, gauges must keep per-process
identity plus rollups, histogram buckets must merge exactly.

Run as: python mp_obs_agg_worker.py <task_index> <coordinator_port> <obs_dir>
"""

import os
import sys


def main() -> None:
    task_index, port, obs_dir = int(sys.argv[1]), sys.argv[2], sys.argv[3]

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
            "--training_steps", "8",
            "--eval_step_interval", "4",
            "--batch_size", "8",
            "--synthetic_data", "1",
            "--log_dir", os.path.join(obs_dir, "logs"),
            "--obs_dir", obs_dir,
        ]
    )
    assert stats is not None and stats["steps"] == 8, stats

    from distributed_tensorflow_tpu import obs
    from distributed_tensorflow_tpu.parallel import distributed as D

    # The live train loop already dropped fleet snapshots at eval
    # boundaries; layer process-distinct histogram traffic on top and
    # re-snapshot so the merge has buckets to add.
    reg = obs.get_registry()
    local_steps = int(reg.counter("train_steps_total", "").value)
    assert local_steps == 8, local_steps
    hist = reg.histogram("mp_obs_seconds", "merge fodder", buckets=(0.1, 1.0))
    for v in ((0.05, 0.3) if task_index == 0 else (0.7, 2.0)):
        hist.observe(v)
    snap_path = obs.write_process_snapshot(obs_dir)
    assert os.path.basename(snap_path) == f"fleet_p{task_index}.json"
    D.barrier("obs_snapshots_written")

    if D.is_chief():
        fleet = obs.FleetAggregator()
        assert fleet.load_dir(obs_dir) == 2
        merged = fleet.export(obs_dir)
        # Counters sum across the fleet.
        total = merged.counter("train_steps_total", "").value
        assert total == 2 * local_steps, total
        # Histogram buckets merged exactly: one obs <= 0.1 (p0's 0.05),
        # three <= 1.0, four lifetime (p1's 2.0 only in the +Inf bucket).
        h = merged.histogram("mp_obs_seconds", "", buckets=(0.1, 1.0))._solo()
        assert h.count == 4, h.count
        assert dict(h.buckets()) == {0.1: 1, 1.0: 3}, h.buckets()
        assert abs(h.total - (0.05 + 0.3 + 0.7 + 2.0)) < 1e-9
        # Gauges keep per-process identity + fleet rollups.
        fam = merged.gauge("train_examples_per_sec", "", labels=("process",))
        procs = sorted(lv[0] for lv, _ in fam.children())
        assert procs == ["0", "1"], procs
        rates = {lv[0]: inst.value for lv, inst in fam.children()}
        rollup = merged.gauge("train_examples_per_sec_sum", "").value
        assert abs(rollup - sum(rates.values())) < 1e-9
        prom = open(os.path.join(obs_dir, "fleet_merged.prom")).read()
        assert f"train_steps_total {2 * local_steps}" in prom, prom[:400]
    D.barrier("obs_fleet_merged")
    # Every process sees the chief's merged export on the shared dir.
    assert os.path.exists(os.path.join(obs_dir, "fleet_merged.prom"))
    assert os.path.exists(os.path.join(obs_dir, "fleet_merged.json"))
    print(f"OBS_AGG_WORKER_{task_index}_OK")


if __name__ == "__main__":
    main()
