#!/usr/bin/env python3
"""Chip smoke: train -> serve on the real TPU through the normal entry points.

    python3 chip_smoke.py                # the real run; needs a TPU
    python3 chip_smoke.py --rehearse_cpu # same phases, toy width, CPU

The quickest proof that the system still starts on the chip. One model (the
403M flagship LM of bench.py: d_model 2048, 16 heads, d_ff 8192, 8 layers,
seq 2048; seeded random weights) goes through the CLIs a user would call:

  probe    what JAX sees. Anything but platform "tpu" ends the run here —
           there is no CPU fallback. The chip count n sizes the rest.
  kernels  the Pallas flash kernels, compiled (interpret=False passed
           explicitly) at the flagship attention shape, forward + backward,
           against the dense f32 reference at bf16 tolerance.
  train    tools/train_lm.py, 20 steps, batch 12 per chip, bundle exported.
  serve    tools/serve_lm.py on that bundle + tools/loadgen.py --url,
           /healthz must say the engine sits on "tpu", 0 recompiles,
           SIGTERM drains to exit 0.
  serve_tp4  (n >= 4) the same bundle under --tp 4.

This parent never imports jax: every phase is a child process, started and
reaped before the next one starts, so exactly one process owns the chip at
any time. A phase that fails, times out or prints a non-finite loss ends the
run non-zero. On success the LAST stdout line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``.

``--rehearse_cpu`` is for debugging the script itself before spending chip
time: it says ``platform=cpu`` on every line it prints and its last line
carries ``"ok": false`` — it can never be read as a pass.

Phase logs and ``summary.json`` (per-phase wall time, the probe record, the
prefix-hit token-match finding) land in ``chiprun_out/chip_smoke/``; the
1.6 GB bundle lives in a temp dir that is removed on exit. No file the run
needs is larger than 16 MiB (the bundle is written in parts), so a machine
that caps the size of one file does not stop it.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")
TRAIN_LM = os.path.join(ROOT, "tools", "train_lm.py")
SERVE_LM = os.path.join(ROOT, "tools", "serve_lm.py")
LOADGEN = os.path.join(ROOT, "tools", "loadgen.py")

# The contract gives the whole run 1200 s, compilation included.
TOTAL_BUDGET_S = 1150.0

# bf16 tolerance for kernel-vs-dense-f32: max |a - b| / max |b|.
KERNEL_TOL = 2e-2

REAL = {
    "kernel_shapes": [
        # (name, batch, seq, heads, head_dim, rope)
        ("packed_d128", 2, 2048, 16, 128, False),
        ("packed_d128_rope", 2, 2048, 16, 128, True),
        ("bhsd_d64", 2, 2048, 16, 64, False),
    ],
    "train": {
        "d_model": 2048, "num_heads": 16, "num_layers": 8, "d_ff": 8192,
        "seq_len": 2048, "learning_rate": 1e-4,
    },
    "batch_per_chip": 12,
    "serve": {"slots": 8, "serve_max_len": 2048, "prefill_len": 512},
    "load": {"prompt_len": 128, "max_new_tokens": 32},
    # tp=4 weight bytes per device over tp=1's: 0.26 measured on the chip.
    "tp4_weight_ratio": 0.5,
}
TOY = {
    "kernel_shapes": [
        ("packed_d128", 1, 256, 2, 128, False),
        ("packed_d128_rope", 1, 256, 2, 128, True),
        ("bhsd_d64", 1, 256, 2, 64, False),
    ],
    "train": {
        "d_model": 64, "num_heads": 4, "num_layers": 2, "d_ff": 128,
        "seq_len": 64, "learning_rate": 3e-3,
    },
    "batch_per_chip": 4,
    "serve": {"slots": 4, "serve_max_len": 64, "prefill_len": 32},
    "load": {"prompt_len": 24, "max_new_tokens": 8},
    # At toy width the replicated embeddings dominate: 0.52 on CPU.
    "tp4_weight_ratio": 0.75,
}


class PhaseFailed(SystemExit):
    def __init__(self, phase: str, why: str):
        print(f"chip_smoke: phase {phase} FAILED: {why}", file=sys.stderr,
              flush=True)
        super().__init__(1)


# ---------------------------------------------------------------------------
# Children (the only code here that imports jax).
# ---------------------------------------------------------------------------


def child_probe(_args) -> int:
    import importlib.metadata

    import jax
    import jaxlib

    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    devices = jax.devices()
    print(json.dumps({
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "count": len(devices),
    }), flush=True)
    return 0


def child_kernels(args) -> int:
    """Flash kernels vs the dense f32 reference. ``interpret`` is passed
    explicitly: False on the chip, so nothing can downgrade the kernels to
    the interpreter and a Mosaic refusal surfaces here."""
    from distributed_tensorflow_tpu.utils.compile_cache import (
        enable_compilation_cache,
    )

    enable_compilation_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_tensorflow_tpu.ops import attention as A
    from distributed_tensorflow_tpu.ops.rope import rope_tables

    interpret = bool(args.rehearse_cpu)
    shapes = (TOY if args.rehearse_cpu else REAL)["kernel_shapes"]
    platform = jax.devices()[0].platform
    worst = 0.0
    for name, b, s, h, d, rope in shapes:
        kq, kw = jax.random.split(jax.random.PRNGKey(len(name)))
        qkv = jax.random.normal(kq, (b, s, 3 * h * d), jnp.float32)
        qkv = qkv.astype(jnp.bfloat16)
        w = jax.random.normal(kw, (b, s, h * d), jnp.float32)
        cos = sin = None
        if rope:
            cos, sin = rope_tables(d, s)
            # bf16 tables: what models/transformer.py passes under bf16 compute.
            cos, sin = cos.astype(jnp.bfloat16), sin.astype(jnp.bfloat16)

        # w is an argument, not a closure: a closed-over 32 MiB constant
        # would be baked into every cached executable.
        def flash_loss(x, w):
            out = A.flash_attention_qkv(
                x, h, causal=True, interpret=interpret,
                rope_cos=cos, rope_sin=sin)
            return jnp.sum(out.astype(jnp.float32) * w), out

        def dense_loss(x, w):
            q, k, v = A._unpack_qkv(x, h, rope_cos=cos, rope_sin=sin)
            bhsd = lambda t: t.transpose(0, 2, 1, 3)
            out = A.dense_attention(bhsd(q), bhsd(k), bhsd(v), causal=True)
            out = out.transpose(0, 2, 1, 3).reshape(b, s, h * d)
            return jnp.sum(out * w), out

        t0 = time.monotonic()
        (_, out), dqkv = jax.jit(
            jax.value_and_grad(flash_loss, has_aux=True))(qkv, w)
        out, dqkv = jax.device_get((out, dqkv))
        dt = time.monotonic() - t0
        (_, ref), dref = jax.jit(
            jax.value_and_grad(dense_loss, has_aux=True))(
                qkv.astype(jnp.float32), w)
        ref, dref = jax.device_get((ref, dref))
        errs = {}
        for what, got, want in (("out", out, ref), ("dqkv", dqkv, dref)):
            got = np.asarray(got, np.float32)
            want = np.asarray(want, np.float32)
            if got.shape != want.shape:
                print(f"kernels[{name}] {what}: shape {got.shape} != "
                      f"{want.shape}", file=sys.stderr)
                return 1
            if not np.isfinite(got).all():
                print(f"kernels[{name}] {what}: non-finite values",
                      file=sys.stderr)
                return 1
            errs[what] = float(
                np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))
        worst = max(worst, *errs.values())
        print(json.dumps({
            "kernel": name, "platform": platform, "interpret": interpret,
            "shape": [b, s, h, d], "rope": rope, "rel_err": errs,
            "compile_and_run_s": round(dt, 2),
            "fused_bwd_scratch_limit": A._fused_bwd_scratch_limit(),
        }), flush=True)
    if worst > KERNEL_TOL:
        print(f"kernels: worst relative error {worst:.4g} > {KERNEL_TOL}",
              file=sys.stderr)
        return 1
    return 0


CHILDREN = {"probe": child_probe, "kernels": child_kernels}


# ---------------------------------------------------------------------------
# Parent: process plumbing.
# ---------------------------------------------------------------------------


class Runner:
    """Starts children in their own process group, logs them under
    OUT_DIR, and guarantees none outlives the run."""

    def __init__(self, env: dict, tag: str):
        self.env = env
        self.tag = tag
        self.live: list[subprocess.Popen] = []
        self.deadline = time.monotonic() + TOTAL_BUDGET_S

    def remaining(self, phase: str, want: float) -> float:
        left = self.deadline - time.monotonic()
        if left <= 0:
            raise PhaseFailed(phase, "run exceeded its time budget")
        return min(want, left)

    def log_path(self, name: str) -> str:
        return os.path.join(OUT_DIR, f"{name}.log")

    def spawn(self, name: str, cmd: list[str]) -> subprocess.Popen:
        log = open(self.log_path(name), "w")
        print(f"chip_smoke[{self.tag}] start {name}: {' '.join(cmd)}",
              flush=True)
        proc = subprocess.Popen(
            cmd, stdout=log, stderr=subprocess.STDOUT, env=self.env,
            cwd=ROOT, start_new_session=True)
        log.close()
        self.live.append(proc)
        return proc

    def reap(self, proc: subprocess.Popen) -> None:
        """Kill whatever is left of the child's process group."""
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
        if proc in self.live:
            self.live.remove(proc)

    def run(self, phase: str, name: str, cmd: list[str],
            timeout: float) -> str:
        """Run one child to completion; returns its output. Non-zero exit
        or timeout fails the phase."""
        proc = self.spawn(name, cmd)
        try:
            rc = proc.wait(self.remaining(phase, timeout))
        except subprocess.TimeoutExpired:
            raise PhaseFailed(
                phase, f"{name} timed out; tail:\n{self.tail(name)}")
        finally:
            self.reap(proc)
        if rc != 0:
            raise PhaseFailed(
                phase, f"{name} exited {rc}; tail:\n{self.tail(name)}")
        with open(self.log_path(name), errors="replace") as fh:
            return fh.read()

    def tail(self, name: str, lines: int = 40) -> str:
        with open(self.log_path(name), errors="replace") as fh:
            return "".join(fh.readlines()[-lines:])

    def close(self) -> None:
        for proc in list(self.live):
            self.reap(proc)


def json_lines(text: str) -> list[dict]:
    out = []
    for line in text.splitlines():
        line = line.strip()
        if line.startswith("{") and line.endswith("}"):
            try:
                out.append(json.loads(line))
            except ValueError:
                pass
    return out


def http_json(url: str, body: dict | None = None, timeout: float = 120.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


# ---------------------------------------------------------------------------
# Phases.
# ---------------------------------------------------------------------------


def phase_probe(run: Runner, self_cmd: list[str], want_platform: str) -> dict:
    out = run.run("probe", "probe", self_cmd + ["--phase", "probe"], 180.0)
    records = json_lines(out)
    if not records:
        raise PhaseFailed("probe", f"no probe record in:\n{out}")
    rec = records[-1]
    print(f"chip_smoke[{run.tag}] probe: platform={rec['platform']} "
          f"device_kind={rec['device_kind']!r} count={rec['count']} "
          f"jax={rec['jax']} jaxlib={rec['jaxlib']} libtpu={rec['libtpu']}",
          flush=True)
    if rec["platform"] != want_platform:
        raise PhaseFailed(
            "probe", f"platform is {rec['platform']!r}, not "
            f"{want_platform!r} — no accelerator, no fallback")
    return rec


def phase_kernels(run: Runner, self_cmd: list[str], sizes: dict) -> list:
    out = run.run("kernels", "kernels", self_cmd + ["--phase", "kernels"],
                  420.0)
    records = [r for r in json_lines(out) if "kernel" in r]
    if len(records) != len(sizes["kernel_shapes"]):
        raise PhaseFailed("kernels", f"expected {len(sizes['kernel_shapes'])} "
                          f"kernel records, got {len(records)}")
    for r in records:
        print(f"chip_smoke[{run.tag}] kernel {r['kernel']}: "
              f"platform={r['platform']} interpret={r['interpret']} "
              f"rel_err={r['rel_err']} t={r['compile_and_run_s']}s",
              flush=True)
    return records


def phase_train(run: Runner, sizes: dict, n: int, bundle: str,
                platform: str, name: str = "train") -> list:
    t = sizes["train"]
    cmd = [
        sys.executable, TRAIN_LM, "--parallelism", "dp", "--attention",
        "flash", "--use_bias", "0",
        "--d_model", str(t["d_model"]), "--num_heads", str(t["num_heads"]),
        "--num_layers", str(t["num_layers"]), "--d_ff", str(t["d_ff"]),
        "--seq_len", str(t["seq_len"]),
        "--batch_size", str(sizes["batch_per_chip"] * n),
        "--learning_rate", str(t["learning_rate"]),
        "--training_steps", "20", "--eval_step_interval", "5",
        "--output", bundle,
    ]
    out = run.run("train", name, cmd, 700.0)
    records = [r for r in json_lines(out) if "loss" in r and "step" in r]
    if [r["step"] for r in records] != [5, 10, 15, 20]:
        raise PhaseFailed("train", f"unexpected step records: {records}")
    losses = [float(r["loss"]) for r in records]
    if not all(math.isfinite(x) for x in losses):
        raise PhaseFailed("train", f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise PhaseFailed("train", f"loss did not fall: {losses}")
    if platform == "tpu":
        # Absent = utils/flops.py does not know this device_kind; >= 1 =
        # the timed window was not drained. Both are bugs, not scores.
        mfu = records[-1].get("mfu")
        if mfu is None or not 0.0 < float(mfu) < 1.0:
            raise PhaseFailed("train", f"last record mfu={mfu!r} not in (0, 1)")
    if not os.path.exists(bundle):
        raise PhaseFailed("train", f"no bundle at {bundle}")
    print(f"chip_smoke[{run.tag}] train: platform={platform} losses={losses} "
          f"last={records[-1]}", flush=True)
    return records


def wait_for_banner(run: Runner, phase: str, name: str,
                    proc: subprocess.Popen, timeout: float) -> str:
    deadline = time.monotonic() + run.remaining(phase, timeout)
    while time.monotonic() < deadline:
        with open(run.log_path(name), errors="replace") as fh:
            for line in fh:
                if line.startswith("serving on "):
                    return line.split()[2]
        if proc.poll() is not None:
            raise PhaseFailed(phase, f"server exited {proc.returncode} before "
                              f"its banner; tail:\n{run.tail(name)}")
        time.sleep(0.5)
    raise PhaseFailed(phase, f"no banner within {timeout:.0f}s; tail:\n"
                      f"{run.tail(name)}")


def phase_serve(run: Runner, sizes: dict, bundle: str, platform: str,
                phase: str, tp: int = 1) -> dict:
    s, load = sizes["serve"], sizes["load"]
    cmd = [
        sys.executable, SERVE_LM, "--model", bundle, "--port", "0",
        "--slots", str(s["slots"]), "--serve_max_len", str(s["serve_max_len"]),
        "--prefill_len", str(s["prefill_len"]),
    ]
    if tp > 1:
        cmd += ["--tp", str(tp)]
    report_file = os.path.join(OUT_DIR, f"{phase}_loadgen.jsonl")
    if os.path.exists(report_file):
        os.remove(report_file)
    server = run.spawn(phase, cmd)
    try:
        url = wait_for_banner(run, phase, phase, server, 700.0)
        health = http_json(url + "/healthz")
        mesh = health.get("mesh", {})
        print(f"chip_smoke[{run.tag}] {phase}: {url} mesh={mesh}", flush=True)
        if mesh.get("platform") != platform:
            raise PhaseFailed(phase, f"/healthz says the engine is on "
                              f"{mesh.get('platform')!r}, not {platform!r}")
        if mesh.get("tp") != tp or mesh.get("devices") != tp:
            raise PhaseFailed(phase, f"/healthz mesh {mesh} is not tp={tp}")
        # One fixed greedy prompt twice: cold, then served from the prefix
        # cache. A token mismatch is RECORDED, not failed — 20 steps of
        # training leaves near-ties everywhere.
        prompt = [(7 * i + 3) % 251 + 2 for i in range(load["prompt_len"])]
        req = {"prompt": prompt, "max_new_tokens": load["max_new_tokens"],
               "temperature": 0.0}
        cold = http_json(url + "/generate", req)["tokens"]
        warm = http_json(url + "/generate", req)["tokens"]
        run.run(phase, f"{phase}_loadgen", [
            sys.executable, LOADGEN, "--url", url, "--num_requests", "8",
            "--concurrency", "4", "--prompt_len", str(load["prompt_len"]),
            "--max_new_tokens", str(load["max_new_tokens"]), "--smoke",
            "--seed", "0", "--report_file", report_file,
        ], 300.0)
        with open(report_file) as fh:
            report = json.loads(fh.readlines()[-1])
        bad = {
            k: report.get(k) for k, want in (
                ("completed", 8), ("shed", 0), ("dropped_without_shed", 0),
                ("stream_aborted", 0), ("recompile_events_total", 0))
            if report.get(k) != want
        }
        if bad:
            raise PhaseFailed(phase, f"loadgen report off contract: {bad}")
        server.send_signal(signal.SIGTERM)
        try:
            rc = server.wait(run.remaining(phase, 90.0))
        except subprocess.TimeoutExpired:
            raise PhaseFailed(phase, "server did not drain after SIGTERM")
        if rc != 0:
            raise PhaseFailed(phase, f"server exited {rc} after SIGTERM; "
                              f"tail:\n{run.tail(phase)}")
    finally:
        run.reap(server)
    result = {
        "mesh": mesh,
        "prefix_hit_tokens_match": cold == warm,
        "serve_prefix_hit_rate": report.get("serve_prefix_hit_rate"),
        "serve_weight_bytes_per_device":
            report.get("serve_weight_bytes_per_device"),
        "throughput_tok_s": report.get("throughput_tok_s"),
        "ttft_ms": report.get("ttft_ms"),
    }
    print(f"chip_smoke[{run.tag}] {phase}: platform={platform} 8/8 completed, "
          f"0 recompiles, prefix_hit_tokens_match={cold == warm}, "
          f"weight_bytes_per_device="
          f"{result['serve_weight_bytes_per_device']}", flush=True)
    return result


# ---------------------------------------------------------------------------
# Entry.
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--rehearse_cpu", action="store_true",
        help="run every phase at toy width on CPU with interpret-mode "
             "kernels; never prints a pass line")
    parser.add_argument("--phase", choices=sorted(CHILDREN),
                        help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.phase:
        return CHILDREN[args.phase](args)

    missing = [p for p in (TRAIN_LM, SERVE_LM, LOADGEN)
               if not os.path.exists(p)]
    if missing:
        print(f"chip_smoke: not a checkout of the repo, missing {missing}",
              file=sys.stderr)
        return 2

    env = dict(os.environ)
    self_cmd = [sys.executable, os.path.abspath(__file__)]
    if args.rehearse_cpu:
        env["JAX_PLATFORMS"] = "cpu"
        self_cmd.append("--rehearse_cpu")
    sizes = TOY if args.rehearse_cpu else REAL
    platform = "cpu" if args.rehearse_cpu else "tpu"

    # A terminated parent still reaps its children (the finally below).
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    os.makedirs(OUT_DIR, exist_ok=True)
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    bundle = os.path.join(work, "lm.msgpack")
    run = Runner(env, tag=f"platform={platform}")
    summary: dict = {"rehearsal": bool(args.rehearse_cpu), "phases": {}}
    t_start = time.monotonic()

    def timed(name, fn, *a, **kw):
        t0 = time.monotonic()
        result = fn(*a, **kw)
        summary["phases"][name] = {
            "wall_s": round(time.monotonic() - t0, 1), "result": result}
        return result

    try:
        probe = timed("probe", phase_probe, run, self_cmd, platform)
        n = int(probe["count"])
        timed("kernels", phase_kernels, run, self_cmd, sizes)
        timed("train", phase_train, run, sizes, n, bundle, platform)
        tp1 = timed("serve", phase_serve, run, sizes, bundle, platform,
                    "serve")
        if n >= 4:
            tp4 = timed("serve_tp4", phase_serve, run, sizes, bundle,
                        platform, "serve_tp4", tp=4)
            b1 = tp1["serve_weight_bytes_per_device"]
            b4 = tp4["serve_weight_bytes_per_device"]
            if not (b1 and b4 and b4 < sizes["tp4_weight_ratio"] * b1):
                raise PhaseFailed(
                    "serve_tp4", f"weight bytes per device {b4} not well "
                    f"below tp=1's {b1}")
    finally:
        run.close()
        shutil.rmtree(work, ignore_errors=True)
        summary["wall_s"] = round(time.monotonic() - t_start, 1)
        with open(os.path.join(OUT_DIR, "summary.json"), "w") as fh:
            json.dump(summary, fh, indent=1)

    if "jax" in sys.modules:
        raise PhaseFailed("parent", "the parent imported jax")
    device = {"platform": probe["platform"], "kind": probe["device_kind"],
              "count": n}
    print(f"chip_smoke[{run.tag}] all phases passed in "
          f"{summary['wall_s']}s: "
          + " ".join(f"{k}={v['wall_s']}s"
                     for k, v in summary["phases"].items()), flush=True)
    if args.rehearse_cpu:
        print(json.dumps({"ok": False, "rehearsal": "platform=cpu, toy width "
                          "— not a chip result", "device": device}))
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
