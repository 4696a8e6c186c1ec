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


def _kernel_line(compiled) -> str:
    """The custom call of the kernel in an optimised module's text."""
    (line,) = [l for l in compiled.as_text().splitlines()
               if "tpu_custom_call" in l and "custom-call(" in l]
    return line


@pytest.mark.parametrize("dtype,slots,kv,group,ps,pps,window,result", [
    # starcoder2-3b as benchmarks/configs runs it: 16 slots of 4096, GQA
    # 24 / 2, pages of 16, the window equal to serve_max_len. Group 12
    # stays on the form it has: q padded to 16 rows a head, bf16 out.
    (jnp.bfloat16, 16, 2, 12, 16, 256, 4096, "bf16[16,2,16,128]"),
    # An f32 pool at its own tile, MHA, a window that skips pages: the
    # row form, flat f32 rows out.
    (jnp.float32, 4, 2, 1, 8, 12, 20, "f32[4,2,128]"),
], ids=["sc2-3b-bf16", "f32-page8-window"])
def test_paged_decode_kernel_compiles_for_v5e(one_chip, dtype, slots, kv,
                                              group, ps, pps, window, result):
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
    assert f"= {result}" in _kernel_line(compiled)


@pytest.mark.parametrize("pages_per_chunk", [8, 32], ids=["chunk8", "chunk32"])
def test_paged_decode_kernel_compiles_for_evabyte_rows(one_chip,
                                                       pages_per_chunk):
    """evabyte-6.5b as benchmarks/configs runs it: 16 slots, 32 kv heads of
    128 and no groups, pages of 16, a composed row of 248 pages (15 windows
    of summaries and one window of K/V rows): the row form, at the 8 pages
    a chunk ``models/transformer._eva_through_table`` picks (a page is 16
    times StarCoder2's) and at the default 32, whose 16 MiB of chunk
    buffers fit only because the call asks Mosaic for the VMEM its shapes
    need (Mosaic refuses a kernel over the limit it was given)."""
    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    slots, kv, ps, pps = 16, 32, 16, 248
    pages = slots * (pps + 8) + 1
    compiled = jax.jit(
        lambda q, k, v, tables, lens: paged_decode_attention(
            q, k, v, tables, lens, pages_per_chunk=pages_per_chunk,
            interpret=False)
    ).lower(
        arg((slots, kv, 1, 128), jnp.bfloat16),
        arg((pages, kv, ps, 128), jnp.bfloat16),
        arg((pages, kv, ps, 128), jnp.bfloat16),
        arg((slots, pps), jnp.int32),
        arg((slots,), jnp.int32),
    ).compile()
    assert "= f32[16,32,128]" in _kernel_line(compiled)
