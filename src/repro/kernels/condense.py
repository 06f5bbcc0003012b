"""Condensation gather kernel: ``y[i] = y[rep_idx[i]]`` (token_to_token
replacement, paper §VI), and the row gather it is built on.

TPU memory is tiled: an f32 array is stored in ``(8, 128)`` tiles (16
rows per tile for bf16, 32 for 8-bit types), so one row cannot be
addressed on its own by a DMA or a vector load. Each grid step
therefore fetches the whole aligned row tile that holds its row — the
BlockSpec index map reads the scalar-prefetched index, so Pallas
pipelines the tile fetches — widens it to f32 in VMEM, where a single
32-bit row can be read at a dynamic offset, and writes that row into an
f32 block of ``bt`` rows, which the last step of the block stores. The
source table stays in HBM, so the gather scales to any table size; the
price is one tile (8–32 rows) read and one grid step per output row.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import out_struct, resolve_interpret

DEFAULT_BT = 256


def tile_rows(dtype) -> int:
    """Rows per HBM tile of ``dtype``: 8 for 32-bit, 16 for 16-bit, 32
    for 8-bit types."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def block_rows(R: int, bt: int) -> int:
    """Rows per grid step: ``bt``, or a divisor of ``R`` below it."""
    bt_ = min(bt, R)
    if R % bt_:
        bt_ = math.gcd(R, bt_)
    return bt_


def gather_step(idx_ref, tile_ref, tile32, rows):
    """Grid step (i, j): write source row ``idx[i*bt + j]`` into row
    ``j`` of ``rows`` [bt, d] f32 (a zero row for a negative index).
    ``tile_ref``: [p, d] tile holding that row (see :func:`gather_specs`);
    ``tile32``: [p, d] f32 VMEM scratch; ``idx_ref``: the scalar-
    prefetched index vector (SMEM)."""
    j = pl.program_id(1)
    t = idx_ref[pl.program_id(0) * rows.shape[0] + j]
    tile32[...] = tile_ref[...].astype(jnp.float32)
    row = tile32[pl.ds(jnp.maximum(t, 0) % tile32.shape[0], 1), :]
    rows[pl.ds(j, 1), :] = jnp.where(t >= 0, row, jnp.zeros_like(row))


def tile_view(x):
    """[T, d] -> [T/p, p, d] with T zero-padded to whole row tiles (a
    free reshape when T is already a multiple of the tile height)."""
    T, d = x.shape
    p = tile_rows(x.dtype)
    if T % p:
        x = jnp.pad(x, ((0, p - T % p), (0, 0)))
    return x.reshape(-1, p, d)


def gather_specs(bt: int, x):
    """Source BlockSpec and VMEM scratch for :func:`gather_step` over
    ``x`` [T, d], on the grid (rows / bt, bt)."""
    p, d = tile_rows(x.dtype), x.shape[1]
    src = pl.BlockSpec(
        (None, p, d),
        lambda i, j, idx: (jnp.maximum(idx[i * bt + j], 0) // p, 0, 0))
    return src, [pltpu.VMEM((p, d), jnp.float32),
                 pltpu.VMEM((bt, d), jnp.float32)]


def _gather_kernel(idx_ref, tile_ref, out_ref, tile32, rows):
    gather_step(idx_ref, tile_ref, tile32, rows)

    @pl.when(pl.program_id(1) == rows.shape[0] - 1)
    def store():
        out_ref[...] = rows[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("out_dtype", "bt",
                                             "interpret"))
def gather_rows(y, rep_idx, *, out_dtype=None, bt: int = DEFAULT_BT,
                interpret: Optional[bool] = None):
    """y: [T, d]; rep_idx: [R] int32 -> y[rep_idx] as ``out_dtype``
    (default ``y.dtype``); a negative index gives a zero row."""
    _, d = y.shape
    R = rep_idx.shape[0]
    bt_ = block_rows(R, bt)
    out_dtype = y.dtype if out_dtype is None else out_dtype
    src, scratch = gather_specs(bt_, y)
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(R // bt_, bt_), in_specs=[src],
            out_specs=pl.BlockSpec((bt_, d), lambda i, j, idx: (i, 0)),
            scratch_shapes=scratch),
        out_shape=out_struct((R, d), out_dtype, y, rep_idx),
        interpret=resolve_interpret(interpret),
    )(rep_idx, tile_view(y))
