"""Kernels of the serving path, compiled for a described (not attached)
TPU v5e at the benchmark's widths: what Mosaic refuses — a slice off the
tiling, too much VMEM — interpret mode on the CPU never sees.

Nothing runs and nothing is timed. The topology is described inside a
fixture, by the one test worker that is handed this file (only one
process at a time may load the TPU's library); every such compile of the
repository belongs in THIS file.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from distributed_tensorflow_tpu.ops.attention import paged_decode_attention

pytestmark = [pytest.mark.serve, pytest.mark.paged]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("dtype,slots,kv,group,ps,pps,window", [
    # starcoder2-3b as benchmarks/configs runs it: 16 slots of 4096, GQA
    # 24 / 2, pages of 16, the window equal to serve_max_len.
    (jnp.bfloat16, 16, 2, 12, 16, 256, 4096),
    # An f32 pool at its own tile, MHA, a window that skips pages.
    (jnp.float32, 4, 2, 1, 8, 12, 20),
], ids=["sc2-3b-bf16", "f32-page8-window"])
def test_paged_decode_kernel_compiles_for_v5e(one_chip, dtype, slots, kv,
                                              group, ps, pps, window):
    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pages = slots * pps + 1
    compiled = jax.jit(
        lambda q, k, v, tables, lens: paged_decode_attention(
            q, k, v, tables, lens, window=window, interpret=False)
    ).lower(
        arg((slots, kv, group, 128), dtype),
        arg((pages, kv, ps, 128), dtype),
        arg((pages, kv, ps, 128), dtype),
        arg((slots, pps), jnp.int32),
        arg((slots,), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_decode_kernel_compiles_for_evabyte_rows(one_chip):
    """evabyte-6.5b as benchmarks/configs runs it: 16 slots, 32 kv heads of
    128 and no groups, pages of 16, a composed row of 248 pages (15 windows
    of summaries and one window of K/V rows), chunks of 16 pages: a page is
    16 times StarCoder2's, so the default 32 would ask for 16.8 MB of
    VMEM (``models/transformer._eva_through_table`` picks the chunk)."""
    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    slots, kv, ps, pps = 16, 32, 16, 248
    pages = slots * (pps + 8) + 1
    compiled = jax.jit(
        lambda q, k, v, tables, lens: paged_decode_attention(
            q, k, v, tables, lens, pages_per_chunk=16, interpret=False)
    ).lower(
        arg((slots, kv, 1, 128), jnp.bfloat16),
        arg((pages, kv, ps, 128), jnp.bfloat16),
        arg((pages, kv, ps, 128), jnp.bfloat16),
        arg((slots, pps), jnp.int32),
        arg((slots,), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
