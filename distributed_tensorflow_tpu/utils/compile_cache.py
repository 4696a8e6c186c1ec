"""Persistent XLA compilation cache and the TPU scoped-VMEM budget.

Every CLI calls :func:`enable_compilation_cache` right after parsing flags,
so repeat runs (train, then the test CLI, then serve) reuse compiled programs
across processes instead of re-paying minutes of XLA:TPU compile each time.

The cache is placed from OUTSIDE, by JAX's own variables:

  JAX_COMPILATION_CACHE_DIR=<dir>        set: this module touches no cache
                                         config at all — JAX reads it itself.
                                         unset: ``<repo>/.jax_cache``, a fixed
                                         path next to the package (the path
                                         is part of the cache key, so it must
                                         not depend on cwd, pid, time or
                                         ``$HOME``).
  JAX_ENABLE_COMPILATION_CACHE=false     off (what the tests set)

  DTF_SCOPED_VMEM_KIB=<n|0>              scoped-VMEM compiler budget (0 =
                                         leave the XLA default alone)
"""

from __future__ import annotations

import os

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

# XLA:TPU's default scoped-VMEM budget is 16 MiB of a v5e core's 128 MiB —
# measured (r5, tools/adam_fusion_probe.py era A/B): raising it to 32 MiB
# lets the compiler emit larger fusions/deeper prefetch around the flash
# custom calls and took the flagship LM step from 74.1% → 77.6% MFU
# (441 → 421 ms/step); 48/64 MiB plateau at the same value. Set via
# LIBTPU_INIT_ARGS, which libtpu snapshots at plugin init — so this must
# run before the first backend touch (every CLI calls
# enable_compilation_cache right after flag parsing, ahead of jax use).
_SCOPED_VMEM_FLAG = "--xla_tpu_scoped_vmem_limit_kib"
_SCOPED_VMEM_DEFAULT_KIB = 32768


def _configure_tpu_vmem_budget() -> None:
    kib = os.environ.get("DTF_SCOPED_VMEM_KIB", str(_SCOPED_VMEM_DEFAULT_KIB))
    if kib in ("0", ""):
        return
    try:
        kib_int = int(kib)
    except ValueError:
        # A malformed override must not turn startup into a crash.
        import warnings

        warnings.warn(
            f"DTF_SCOPED_VMEM_KIB={kib!r} is not an integer; using "
            f"{_SCOPED_VMEM_DEFAULT_KIB}",
            stacklevel=3,
        )
        kib_int = _SCOPED_VMEM_DEFAULT_KIB
    existing = os.environ.get("LIBTPU_INIT_ARGS", "")
    if _SCOPED_VMEM_FLAG in existing:
        return  # operator already chose a value — respect it
    # libtpu snapshots its init args at plugin init: writing the env var
    # AFTER the backend is up would not change the budget in force, but
    # ops/attention._scoped_vmem_budget_kib reads this env var — a late
    # write would make the scratch gate size 4 MB fusions for a budget
    # the compiler doesn't actually have (a Mosaic scratch overflow at
    # the 16k D=32 remat shape, per the r5 A/B record). Leave the env
    # alone so the gate sizes for the real (default) budget. The check
    # rides a jax-private symbol (no public "is the backend up yet"
    # exists); if a future jax moves it, treat the state as unknown and
    # SKIP the write — startup must not crash, and the conservative gate
    # is the safe one.
    try:
        from jax._src.xla_bridge import backends_are_initialized
    except ImportError:
        import warnings

        warnings.warn(
            "jax._src.xla_bridge.backends_are_initialized is gone in this "
            "jax version; skipping the scoped-VMEM budget raise "
            f"({_SCOPED_VMEM_FLAG} stays at the XLA default — expect a few "
            "MFU points on TPU). Set LIBTPU_INIT_ARGS yourself to restore "
            "it, and update _configure_tpu_vmem_budget for this jax.",
            stacklevel=3,
        )
        return
    if backends_are_initialized():
        return
    os.environ["LIBTPU_INIT_ARGS"] = (
        f"{existing} {_SCOPED_VMEM_FLAG}={kib_int}".strip()
    )


def enable_compilation_cache() -> str:
    """Apply the scoped-VMEM budget and make sure JAX's persistent
    compilation cache has a directory (module docstring: JAX's own
    ``JAX_COMPILATION_CACHE_DIR`` wins untouched, else
    ``<repo>/.jax_cache``). Returns the directory in force. Safe to call
    repeatedly. The VMEM budget rides LIBTPU_INIT_ARGS, which libtpu
    snapshots at plugin init — call this BEFORE the first jax backend touch
    (every CLI does, right after flag parsing). Called after backend init it
    leaves LIBTPU_INIT_ARGS untouched (the budget in force stays at the XLA
    default AND the attention gate keeps sizing for that default —
    ops/attention._fused_bwd_scratch_limit).

    It also installs the program's start-up spans
    (``obs.install_runtime_spans``: every trace, lowering, XLA compile and
    garbage collection from here on), which touch no backend."""
    _configure_tpu_vmem_budget()
    from distributed_tensorflow_tpu.obs import install_runtime_spans

    install_runtime_spans()
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
