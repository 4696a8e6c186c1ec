"""One cell, once, in a new process.

    python -m benchmarks.run --workload <name> --seed <n> --seconds <s> --trace <0|1>
    python -m benchmarks.run --check

Loads, warms up, measures for ``--seconds``, checks what the timed path
produced against the plain reference, and prints as its last line of
standard output one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (and ``breakdown`` in a traced run), then
``compared``. A run that finds no TPU, or fewer chips than the cell asks
for, exits non-zero and prints no result; it never falls back to the CPU.

``--rehearse-cpu`` drives the same code at toy width on the CPU, for
finding faults in the harness; its line says ``correct: false`` and can
never be read as a result.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import time

from benchmarks import common, manifest

SCRATCH = os.path.join(common.ROOT, ".benchscratch")


def _parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--check", action="store_true",
                   help="check BENCHMARK.json and every data file, no run")
    p.add_argument("--workload")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="toy width on the CPU; prints correct: false")
    p.add_argument("--control", default=None,
                   help="put the control (the reference in this lower "
                        "precision) in the program's place: its numbers go "
                        "through the comparison and correct comes out false")
    p.add_argument("--fault", default=None,
                   help="break the timed path underneath (tests only)")
    return p.parse_args(argv)


def _backend(args, chips: int):
    """Configure JAX before its first use; the devices the cell gets."""
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={chips}").strip()
        os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
    from distributed_tensorflow_tpu.utils.compile_cache import (
        enable_compilation_cache,
    )

    cache_dir = enable_compilation_cache()  # the program's own start-up
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"benchmarks.run: no backend: {e}", file=sys.stderr)
        raise SystemExit(3)
    want = "cpu" if args.rehearse_cpu else "tpu"
    if devices[0].platform != want:
        print(f"benchmarks.run: platform is {devices[0].platform!r}, not "
              f"{want!r}: no accelerator, no fallback", file=sys.stderr)
        raise SystemExit(3)
    if len(devices) < chips:
        print(f"benchmarks.run: the cell asks for {chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        raise SystemExit(3)
    return devices, cache_dir


def read_layer_metrics(man: dict, cell_name: str, collected: dict) -> dict:
    """Every per-layer metric whose reader finds something to read in this
    cell. A share of a roofline or of a peak above 105% is a fault in the
    count or the clock: the harness refuses to print it."""
    e2e = {e["name"]: e for e in man["end_to_end"]}
    out = {}
    for p in man["per_layer"]:
        cells = p.get("workloads") or e2e[p["moves"]].get("workloads")
        if cells and cell_name not in cells:
            continue
        value = manifest.load_reader(p["name"]).read(collected)
        if value is None:
            continue
        if p["unit"] == "%" and value > 105.0 and (
                "roofline" in p["name"] or "mfu" in p["name"]):
            raise SystemExit(
                f"benchmarks.run: {p['name']} reads {value:.1f}% > 105%: "
                "the operations or bytes are counted too high, or the time "
                "leaves out part of the work")
        out[p["name"]] = common.metric(value, p["unit"])
    return out


def build_ctx(man: dict, args, devices, t_start: float) -> dict:
    """What a cell's runner gets: the cell's data files, resolved by name,
    and the run's arguments."""
    cell, config, traffic_cfg, cell_file = manifest.cell_files(
        man, args.workload)
    seconds = float(args.seconds if args.seconds is not None
                    else man["run_seconds"])
    toy = bool(args.rehearse_cpu)
    model_cfg = dict(config["transformer_config"])
    section = {"serve": "serve_config", "train": "train_config"}[
        config["kind"]]
    run_cfg = dict(config[section])
    if toy:
        model_cfg.update(config["toy"]["transformer_config"])
        run_cfg.update(config["toy"][section])
    os.makedirs(SCRATCH, exist_ok=True)
    return {
        "cell": cell, "config": config, "traffic": traffic_cfg,
        "limits": cell_file["limits"], "model_cfg": model_cfg,
        "serve_cfg": run_cfg, "train_cfg": run_cfg,
        "seed": int(args.seed), "seconds": seconds, "trace": bool(args.trace),
        "toy": toy, "devices": devices[: int(cell["chips"])],
        "process_start": t_start, "scratch": SCRATCH,
        "control": args.control, "fault": args.fault,
    }


def main(argv=None) -> int:
    t_start = common.process_start_time()
    args = _parse(argv)
    if args.check:
        problems = manifest.check()
        for line in problems:
            print(f"check: {line}", file=sys.stderr)
        print(f"check: {'FAILED' if problems else 'ok'} "
              f"({len(problems)} problems)")
        return 1 if problems else 0
    if not args.workload:
        print("benchmarks.run: --workload or --check", file=sys.stderr)
        return 2
    if not os.path.isdir(os.path.join(common.ROOT,
                                      "distributed_tensorflow_tpu")):
        print("benchmarks.run: the program is not in this checkout",
              file=sys.stderr)
        return 2
    man = manifest.load_manifest()
    cell, config, _, _ = manifest.cell_files(man, args.workload)
    devices, _ = _backend(args, int(cell["chips"]))
    toy = bool(args.rehearse_cpu)
    ctx = build_ctx(man, args, devices, t_start)
    runner = importlib.import_module(config["runner"])
    res = runner.run(ctx)

    device = common.device_record(devices)
    device["memory_peak_bytes"] = int(res["memory_peak_bytes"])
    breakdown = None
    if args.trace:
        collected = res["collected"]
        collected["peaks"] = _peaks(devices, toy)
        collected["chips"] = int(cell["chips"])
        tr = collected["trace"] = _reduce_trace(collected)
        if not toy and not tr.get("busy_s"):
            print("benchmarks.run: the trace shows no device operation",
                  file=sys.stderr)
            return 4
        metrics = read_layer_metrics(man, cell["name"], collected)
        device["busy_s"] = float(tr.get("busy_s", 0.0))
        device["window_s"] = float(tr.get("window_s", 0.0))
        breakdown = tr.get("breakdown")
    else:
        metrics = {}
        for e in man["end_to_end"]:
            if e.get("workloads") and cell["name"] not in e["workloads"]:
                continue
            value = res["end_to_end"].get(e["name"])
            if value is None:
                print(f"benchmarks.run: no reading of {e['name']}",
                      file=sys.stderr)
                return 4
            metrics[e["name"]] = common.metric(value, e["unit"])
    compared = res["compared"]
    correct = all(c["ok"] for c in compared.values()) and not toy
    result = {"correct": bool(correct), "attempted": int(res["attempted"]),
              "failed": int(res["failed"]), "metrics": metrics,
              "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    print(json.dumps({"extra": res.get("extra", {}),
                      "end_to_end": res["end_to_end"],
                      "wall_s": time.time() - t_start}), flush=True)
    common.emit(result, compared)
    return 0


def _reduce_trace(collected: dict) -> dict:
    """The reduction of the run's trace, which is then deleted (a trace of
    three seconds of serving is tens of MB)."""
    trace_dir = collected.get("trace_dir")
    if not trace_dir:
        return {}
    from benchmarks import trace_reduce

    loaded = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)
    return trace_reduce.reduce(loaded)


def _peaks(devices, toy: bool) -> dict:
    from benchmarks import peaks

    if toy:  # a rehearsal has no peak: the v5e's stand in, and say so
        return dict(peaks.lookup("TPU v5 lite"), rehearsal=True)
    return peaks.lookup(devices[0].device_kind)


if __name__ == "__main__":
    sys.exit(main())
