"""Driver-entry-point contract tests.

``__graft_entry__.dryrun_multichip(n)`` is a structural check that always
runs on an n-device virtual CPU mesh, whatever hardware is attached and
whatever the caller did to jax first — it never opens a chip. These tests
drive its device bootstrap in subprocesses with the pytest process's own
JAX/XLA overrides stripped, the way the driver calls it.
"""

import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, **env_extra) -> str:
    env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS")
    }
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    env.update(env_extra)
    proc = subprocess.run(
        [sys.executable, "-c", code],
        cwd=_REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, f"rc={proc.returncode}\n{proc.stdout}\n{proc.stderr}"
    return proc.stdout


def test_virtual_cpu_devices_in_bare_process():
    # No JAX_PLATFORMS, no XLA_FLAGS: the bootstrap selects CPU itself,
    # before any backend (and so any attached chip) is touched.
    out = _run(
        "from __graft_entry__ import _virtual_cpu_devices\n"
        "jax = _virtual_cpu_devices(4)\n"
        "devs = jax.devices()\n"
        "print('PLATFORM', devs[0].platform, len(devs))\n"
    )
    assert "PLATFORM cpu 4" in out


def test_virtual_cpu_devices_after_backend_already_initialized():
    # The driver (or its harness) may touch jax.devices() before calling the
    # entry point; a too-small live client is dropped and rebuilt on CPU.
    out = _run(
        "import jax\n"
        "n_before = len(jax.devices())\n"
        "from __graft_entry__ import _virtual_cpu_devices\n"
        "jax = _virtual_cpu_devices(4)\n"
        "devs = jax.devices()\n"
        "assert n_before < 4, n_before\n"
        "print('PLATFORM', devs[0].platform, len(devs))\n",
        JAX_PLATFORMS="cpu",
    )
    assert "PLATFORM cpu 4" in out


def test_virtual_cpu_devices_keeps_a_sufficient_live_client():
    # Under the conftest-style env the 8 virtual CPU devices already exist;
    # the bootstrap must leave them alone (no clear, no reconfigure).
    out = _run(
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "first = jax.devices()[0]\n"
        "from __graft_entry__ import _virtual_cpu_devices\n"
        "jax2 = _virtual_cpu_devices(8)\n"
        "assert jax2.devices()[0] is first  # same live client, not rebuilt\n"
        "print('KEPT', len(jax2.devices()))\n",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
    )
    assert "KEPT 8" in out
