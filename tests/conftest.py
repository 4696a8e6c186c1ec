"""Test env: force JAX onto 8 virtual CPU devices BEFORE any backend init.

This replaces the reference's nonexistent multi-node test story (SURVEY §4):
sharding/collective code paths are exercised on a single host via
``--xla_force_host_platform_device_count=8``.

The platform is pinned through ``jax.config.update`` as well as by tier-1's
``JAX_PLATFORMS=cpu``, so a bare ``pytest`` on a machine with a chip never
takes it. XLA_FLAGS is read lazily at CPU-client creation, so setting it here
is early enough.
"""

import os

# Tests invoke CLI mains, which enable the persistent compilation cache —
# keep test runs from writing compiled programs into the checkout.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def make_string_const_node(name: bytes, payload: bytes) -> bytes:
    """Serialized GraphDef NodeDef: a DT_STRING Const (the real 2015 pb's
    ``DecodeJpeg/contents`` feed node) — shared by the graphdef-import and
    golden-fixture tests so the wire encoding lives in one place."""
    from distributed_tensorflow_tpu.models import graphdef_import as gd

    tensor = gd._field(1, 0, 7) + gd._field(8, 2, gd._field(1, 2, payload))
    attr = gd._field(1, 2, b"value") + gd._field(2, 2, gd._field(8, 2, tensor))
    node = (
        gd._field(1, 2, name)
        + gd._field(2, 2, b"Const")
        + gd._field(5, 2, attr)
    )
    return gd._field(1, 2, node)
