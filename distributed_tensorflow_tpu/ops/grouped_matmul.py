"""Grouped matrix product over ragged groups of rows: the routed experts'
two products (``models/moe.py``).

``xs`` (m, k) holds rows sorted by group, ``counts`` (g,) how many rows each
group has (their sum at most m), ``w`` one matrix a group: (g, k, n), or
(g, n, k) with ``transpose_rhs`` (the contraction over the matrix's LAST
axis, as a checkpoint's (out, in) linear layers lie). Row r of group e gives
``xs[r] @ w[e]``; rows behind the last group give nothing defined.

A Pallas kernel in the manner of MegaBlocks' grouped matmul (Gale et al.,
arXiv:2211.15841; ``jax.experimental.pallas.ops.tpu.megablox`` is the
reference it was measured beside). The work list is the (group, row tile)
pairs in which a group has rows, in order, made outside the kernel and
handed in as scalars: the grid walks (column tile, pair), a group's matrix
is copied once a column tile in blocks of its whole contraction, rows of
other groups in the tile are masked off the result, and a group with no row
is never copied: the bytes are those of the groups that have a row, the
FLOPs those of the tiles they fall in. A decode round's few rows a group
make it a stream of the touched groups' weights, bound by HBM.

Why not ``jax.lax.ragged_dot``: XLA:TPU's kernel for it reads
16 groups of 2048 x 4096 at 84% of the HBM rate (``zaya1-8b``) and 128
groups of 2688 x 1856 at 9% (384 rows: 16.6 ms where the bytes need 1.5; a
1.3 GB relayout of the weights a call among it, because an array whose last
axis is no whole number of 128 lanes does not lie as the kernel wants it):
my chip runs, PR 37. This kernel reads the second at 89%.

:func:`grouped_matmul_fits` is the rule on shapes (the same off the TPU, in
interpret mode, as on it). Matrices off the tile are refused by name, here
and, for an expert layer's widths, by ``TransformerConfig``: padding them a
call would be the relayout again.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_tensorflow_tpu.ops.attention import _sublane_rows

__all__ = ["grouped_matmul", "grouped_matmul_fits"]

ROW_TILE = 128  # rows of xs a grid cell multiplies (fewer where m is)
BLOCK_BYTES = 7 << 19  # 3.5 MiB of one group's matrix a grid cell


def grouped_matmul_fits(w, transpose_rhs: bool = False) -> bool:
    """Whether :func:`grouped_matmul` takes the matrices ``w`` as they lie:
    the contraction and the result width whole numbers of 128 lanes where
    they are an array's last axis, and of the dtype's sublane tile where
    they are its second-last."""
    k, n = (w.shape[2], w.shape[1]) if transpose_rhs else w.shape[1:]
    rows = _sublane_rows(w.dtype)
    if transpose_rhs:  # w's last axis is k, the result's n
        return k % 128 == 0 and n % rows == 0
    return n % 128 == 0 and k % rows == 0


def _column_tile(k: int, n: int, itemsize: int) -> int:
    """Columns of the result a grid cell forms: a whole number of 128 lanes
    whose block of a group's matrix is about ``BLOCK_BYTES``, a divisor of
    ``n`` where one is near."""
    cap = max(128, BLOCK_BYTES // (k * itemsize) // 128 * 128)
    if n <= cap:
        return n
    for tn in range(cap, cap // 2, -128):
        if n % tn == 0:
            return tn
    return cap


def _work_list(counts, m_tiles: int, tm: int):
    """(offsets (g + 1,), group of each pair, row tile of each pair, number
    of pairs): the (group, row tile) pairs in which a group has rows, by
    group and tile; entries behind the last pair repeat it, so that the
    kernel's cells there copy nothing new."""
    g = counts.shape[0]
    ends = jnp.cumsum(counts)
    starts = ends - counts
    first = starts // tm
    tiles = jnp.where(counts > 0, (ends - 1) // tm - first + 1, 0)
    upto = jnp.cumsum(tiles)
    n_pairs = upto[-1]
    p = jnp.minimum(jnp.arange(g + m_tiles - 1), jnp.maximum(n_pairs - 1, 0))
    group = jnp.minimum(jnp.searchsorted(upto, p, side="right"), g - 1)
    tile = jnp.clip(first[group] + p - (upto - tiles)[group], 0, m_tiles - 1)
    offsets = jnp.concatenate([jnp.zeros(1, counts.dtype), ends])
    return (offsets.astype(jnp.int32), group.astype(jnp.int32),
            tile.astype(jnp.int32), n_pairs.astype(jnp.int32).reshape(1))


def _kernel(offsets, group, tile, n_pairs, x_ref, w_ref, o_ref, *, tm,
            transpose_rhs):
    p = pl.program_id(1)

    @pl.when(p < n_pairs[0])
    def _():
        e, t = group[p], tile[p]
        acc = jax.lax.dot_general(
            x_ref[...], w_ref[...],
            (((1,), (1 if transpose_rhs else 0,)), ((), ())),
            preferred_element_type=jnp.float32)
        row = t * tm + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        mine = (row >= offsets[e]) & (row < offsets[e + 1])
        # The first pair of a row tile starts its block of the result; the
        # later ones (other groups in the same tile) add their rows to it.
        opens = (p == 0) | (tile[jnp.maximum(p - 1, 0)] != t)

        @pl.when(opens)
        def _():
            o_ref[...] = jnp.where(mine, acc, 0.0)

        @pl.when(jnp.logical_not(opens))
        def _():
            o_ref[...] = jnp.where(mine, acc, o_ref[...])


def grouped_matmul(xs, w, counts, *, transpose_rhs: bool = False,
                   interpret: bool | None = None):
    """``xs`` (m, k) @ ``w`` by ragged groups, (m, n) in float32 (the module
    docstring). Rows at and behind ``counts.sum()`` hold nothing defined."""
    m, k = xs.shape
    n = w.shape[1] if transpose_rhs else w.shape[2]
    if w.shape[0] != counts.shape[0] or (
            w.shape[2] if transpose_rhs else w.shape[1]) != k:
        raise ValueError(
            f"xs {xs.shape} / w {w.shape} / counts {counts.shape} do not fit")
    if not grouped_matmul_fits(w, transpose_rhs):
        raise ValueError(
            f"matrices {w.shape} of {w.dtype} are off the tile "
            "(grouped_matmul_fits)")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    rows = _sublane_rows(xs.dtype)
    tm = min(ROW_TILE, -(-m // rows) * rows)
    padded = -(-m // tm) * tm
    if padded != m:
        xs = jnp.pad(xs, ((0, padded - m), (0, 0)))
    out = _call(xs, w, counts.astype(jnp.int32), tm=tm,
                tn=_column_tile(k, n, w.dtype.itemsize),
                transpose_rhs=bool(transpose_rhs), interpret=bool(interpret))
    return out[:m]


# Jitted like ops.attention's calls: a model's layers trace the kernel once.
@functools.partial(
    jax.jit, static_argnames=("tm", "tn", "transpose_rhs", "interpret"))
def _call(xs, w, counts, *, tm, tn, transpose_rhs, interpret):
    m, k = xs.shape
    g = w.shape[0]
    n = w.shape[1] if transpose_rhs else w.shape[2]
    m_tiles = m // tm
    meta = _work_list(counts, m_tiles, tm)
    w_block = (None, tn, k) if transpose_rhs else (None, k, tn)
    w_index = ((lambda j, p, off, grp, til, np_: (grp[p], j, 0))
               if transpose_rhs
               else (lambda j, p, off, grp, til, np_: (grp[p], 0, j)))
    # Two buffers of each block, and room for the product before it is
    # masked into the result.
    need = (2 * (tm * k * xs.dtype.itemsize + tn * k * w.dtype.itemsize
                 + tm * tn * 4) + 2 * tm * tn * 4)
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(pl.cdiv(n, tn), g + m_tiles - 1),
            in_specs=[
                pl.BlockSpec((tm, k),
                             lambda j, p, off, grp, til, np_: (til[p], 0)),
                pl.BlockSpec(w_block, w_index),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda j, p, off, grp, til, np_: (til[p], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=max(16 << 20, need + (4 << 20))),
        interpret=interpret,
        name="grouped_matmul",
    )(*meta, xs, w)
