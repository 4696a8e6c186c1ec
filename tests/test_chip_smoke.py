"""chip_smoke.py contract, as far as a box without a TPU can check it.

The real run needs the chip (the driver and the builder run it there); here:
the parent never imports jax, a box with no TPU exits non-zero with no pass
line and without running a phase on CPU, the explicit CPU rehearsal drives
every phase at toy width and still cannot be read as a pass, and the
launchers/tables the smoke leans on refuse what they must refuse.
"""

import json
import os
import subprocess
import sys
import types

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMOKE = os.path.join(_REPO, "chip_smoke.py")


def _env(**extra):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false")
    env.update(extra)
    return env


def _last_json(stdout: str):
    lines = [ln for ln in stdout.splitlines() if ln.strip()]
    try:
        return json.loads(lines[-1]) if lines else None
    except ValueError:
        return None


def test_parent_module_imports_no_jax():
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, %r); import chip_smoke; "
         "assert 'jax' not in sys.modules; "
         "assert 'distributed_tensorflow_tpu' not in sys.modules" % _REPO],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr


def test_no_tpu_exits_nonzero_without_a_pass_line(tmp_path):
    proc = subprocess.run(
        [sys.executable, _SMOKE], env=_env(), cwd=str(tmp_path),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "platform=cpu" in proc.stdout  # the probe says where it landed
    assert "start kernels" not in proc.stdout  # no phase ran on CPU
    assert "start train" not in proc.stdout
    last = _last_json(proc.stdout)
    assert not (isinstance(last, dict) and last.get("ok"))
    assert '"ok": true' not in proc.stdout


def test_script_alone_is_not_a_checkout(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    alone.write_bytes(open(_SMOKE, "rb").read())
    proc = subprocess.run(
        [sys.executable, str(alone)], env=_env(), cwd=str(tmp_path),
        capture_output=True, text=True, timeout=60)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


@pytest.mark.slow  # five jax-booting children, ~50 s: over tier-1's per-case norm
def test_cpu_rehearsal_runs_every_phase_and_is_never_a_pass():
    proc = subprocess.run(
        [sys.executable, _SMOKE, "--rehearse_cpu"], env=_env(),
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for phase in ("probe", "kernels", "train", "serve", "serve_loadgen"):
        assert f"start {phase}:" in proc.stdout
    assert "8/8 completed, 0 recompiles" in proc.stdout
    last = _last_json(proc.stdout)
    assert last["ok"] is False and last["device"]["platform"] == "cpu"
    assert '"ok": true' not in proc.stdout


def test_url_mode_loadgen_imports_initialise_no_backend():
    """loadgen --url runs beside the server that holds the chip: its
    imports (the package for metric names) must never create a backend."""
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path[:0] = [%r, %r]\n"
         "import loadgen\n"
         "from distributed_tensorflow_tpu.obs.export import "
         "parse_prometheus_text\n"
         "from distributed_tensorflow_tpu.serve import metric_names\n"
         "from jax._src import xla_bridge\n"
         "assert not xla_bridge.backends_are_initialized()\n"
         % (_REPO, os.path.join(_REPO, "tools"))],
        env=_env(), capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr


def test_peak_tables_refuse_an_unknown_tpu():
    from distributed_tensorflow_tpu.utils import flops

    v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert flops.chip_peak_flops(v5e) == 197e12
    assert flops.chip_hbm_bandwidth(v5e) == 819e9
    cpu = types.SimpleNamespace(platform="cpu", device_kind="cpu")
    assert flops.chip_peak_flops(cpu) is None
    assert flops.chip_hbm_bandwidth(cpu) is None
    unknown = types.SimpleNamespace(platform="tpu", device_kind="TPU v99")
    with pytest.raises(ValueError, match="TPU v99"):
        flops.chip_peak_flops(unknown)
    with pytest.raises(ValueError, match="TPU v99"):
        flops.chip_hbm_bandwidth(unknown)


def test_fleet_chip_pool_one_chip_per_replica(monkeypatch):
    """serve_fleet on a chip host: each replica child gets its own chip
    through libtpu's visibility variables, and a launch that cannot fit
    fails before anything is spawned."""
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    import serve_fleet

    # Children held to the CPU: no probe child, no assignment.
    assert serve_fleet.ChipPool({"JAX_PLATFORMS": "cpu"}).chips == 0

    monkeypatch.setattr(
        serve_fleet.ChipPool, "_count", staticmethod(lambda env: 2))
    pool = serve_fleet.ChipPool({"PATH": os.environ.get("PATH", "")})
    pool.require(2, ["--demo"])
    with pytest.raises(ValueError, match="3 replicas need 3 chips"):
        pool.require(3, ["--demo"])
    with pytest.raises(ValueError, match="--tp 2"):
        pool.require(1, ["--demo", "--tp", "2"])

    show = [sys.executable, "-c",
            "import os, time; print(os.environ['TPU_VISIBLE_CHIPS'], "
            "os.environ['TPU_CHIPS_PER_PROCESS_BOUNDS'], "
            "os.environ['TPU_PROCESS_BOUNDS'], flush=True); time.sleep(30)"]
    procs = [pool.spawn(show), pool.spawn(show)]
    try:
        seen = [p.stdout.readline().split() for p in procs]
        assert seen == [["0", "1,1,1", "1,1,1"], ["1", "1,1,1", "1,1,1"]]
        with pytest.raises(RuntimeError, match="held by live replicas"):
            pool.spawn(show)
        procs[0].kill()
        procs[0].wait(10)
        again = pool.spawn(show)  # the dead replica's chip is free again
        procs.append(again)
        assert again.stdout.readline().split()[0] == seen[0][0]
    finally:
        for p in procs:
            p.kill()
            p.wait(10)
