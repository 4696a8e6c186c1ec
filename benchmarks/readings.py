"""Readings for setting a cell's limits: the program's numbers and the
control's over many seeds in ONE process (set-up is long), at the cell's own
size. Not part of a run; the benchmark's command never calls it.

    python -m benchmarks.readings --workload <name> --seeds 1,2,3 \\
        --seconds 25 --control int8 [--fault half_batch]

With ``--control`` each line's ``compared`` holds the control's numbers (it
stands in the program's place) and ``extra`` the program's beside them.
``--dump`` keeps a serving cell's per-token readings, ``--keep-trace`` the
small recorded form of a traced run's trace (what the CPU test of the trace
reduction reads), both under ``chiprun_out/``. ``--rates`` sweeps an open
loop's offered rate, once, to find the knee its traffic file then states.
"""

from __future__ import annotations

import argparse
import gzip
import importlib
import json
import os
import shutil
import sys
import time

from benchmarks import common, manifest, run

OUT = os.path.join(common.ROOT, "chiprun_out")


def _dump_rows(rows: dict, path: str) -> None:
    import numpy as np

    os.makedirs(os.path.dirname(path), exist_ok=True)
    cat = lambda k: np.concatenate(rows[k]) if rows[k] else np.zeros(0)
    np.savez_compressed(
        path, margin=cat("margin"), served=cat("served"),
        control=cat("control"),
        request=np.concatenate([np.full(len(g), i)
                                for i, g in enumerate(rows["served"])]))


def _keep_trace(trace_dir: str, path: str) -> None:
    from benchmarks import trace_reduce

    loaded = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with gzip.open(path, "wt") as fh:
        json.dump(trace_reduce.record_small(loaded, 0.15), fh)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--control", default=None)
    p.add_argument("--fault", default=None)
    p.add_argument("--rehearse-cpu", action="store_true")
    p.add_argument("--trace", type=int, default=0)
    p.add_argument("--dump", action="store_true")
    p.add_argument("--keep-trace", action="store_true")
    p.add_argument("--rates", default=None)
    args = p.parse_args(argv)
    man = manifest.load_manifest()
    cell, config, _, _ = manifest.cell_files(man, args.workload)
    devices, _ = run._backend(args, int(cell["chips"]))
    runner = importlib.import_module(config["runner"])
    rates = [float(x) for x in args.rates.split(",")] if args.rates else [None]
    seeds = [int(s) for s in args.seeds.split(",")]
    for rate, seed in ((r, s) for r in rates for s in seeds):
        args.seed = seed
        ctx = run.build_ctx(man, args, devices, time.time())
        if rate is not None:
            ctx["traffic"] = dict(ctx["traffic"], rate_per_s=rate)
        res = runner.run(ctx)
        tag = f"{args.workload}-{seed}" + (f"-{args.fault}" if args.fault
                                           else "")
        if args.dump and res.get("check_rows"):
            _dump_rows(res["check_rows"],
                       os.path.join(OUT, "dumps", tag + ".npz"))
        trace_dir = res["collected"].get("trace_dir")
        if trace_dir:
            if args.keep_trace:
                _keep_trace(trace_dir,
                            os.path.join(OUT, "traces", tag + ".json.gz"))
            shutil.rmtree(trace_dir, ignore_errors=True)
        print(json.dumps({
            "seed": seed, "rate": rate, "workload": args.workload,
            "fault": args.fault, "control": args.control,
            "compared": {k: v["value"] for k, v in res["compared"].items()},
            "ok": {k: v["ok"] for k, v in res["compared"].items()},
            "extra": res["extra"], "end_to_end": res["end_to_end"],
            "attempted": res["attempted"], "failed": res["failed"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
