"""Fused gate-mask → dedup-pack → quantize kernel (DESIGN.md §14).

The dedup wire's hot pre-dispatch path used to be three separate HBM
round-trips: scatter the unique payload rows into the ``[N, C_u, d]``
wire buffer (the gate mask folded into the slot map), then a cast pass,
then (for f8) a block-scale pass. Given the inverse slot→token map
(``tok``, −1 = empty slot — cheap to build, it is an int scatter with no
``d``-wide payload), the whole thing is one gather-shaped pass: each
block of wire slots is gathered from the token table (the tiled row
gather of :mod:`repro.kernels.condense`; the table stays in HBM),
empty slots are masked to zero rows, and the wire-dtype payload — plus
the per-``SCALE_BLOCK`` f32 scale sideband for f8e4m3 — is written
directly.

Bit-compatibility contract: the gather form equals the historical
scatter-add-onto-zeros build because every occupied slot has exactly one
contributing token, and the f8 codec formula (f32 accumulate → per-block
abs-max → guarded divide) is shared verbatim with
:func:`repro.comm.dtypes.quantize_rows` — the pure-jnp fallback and
:func:`repro.kernels.ref.pack_quantize_ref` are bit-for-bit targets,
not allclose targets.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.comm import dtypes as wdt
from repro.kernels import out_struct, resolve_interpret
from repro.kernels.condense import (block_rows, gather_rows, gather_specs,
                                    gather_step, tile_view)

DEFAULT_BT = 256


def _pack_quant_kernel(idx_ref, tile_ref, q_ref, sct_ref, tile32, rows, *,
                       block: int):
    """f8 variant: gather + mask row by row, then per-``block`` scales
    once the block of rows is complete. q: [bt, d_pad] f8; sct:
    [d_pad/block, bt] f32 (scales transposed, so the per-block
    reduction runs over sublanes). Formula mirrors
    repro.comm.dtypes.quantize_rows exactly."""
    gather_step(idx_ref, tile_ref, tile32, rows)

    @pl.when(pl.program_id(1) == rows.shape[0] - 1)
    def quantize():
        bt, dp = rows.shape
        blocks = rows[...].T.reshape(dp // block, block, bt)
        amax = jnp.max(jnp.abs(blocks), axis=1)             # [nb, bt]
        # reciprocal multiply, like dtypes.quantize_rows (bitwise contract)
        scale = jnp.where(amax > 0, amax * (1.0 / wdt.F8_MAX), 1.0) \
            .astype(jnp.float32)
        q = (blocks / scale[:, None, :]).reshape(dp, bt).T
        q_ref[...] = q.astype(q_ref.dtype)
        sct_ref[...] = scale


@functools.partial(jax.jit, static_argnames=("wire_dtype", "bt",
                                             "interpret"))
def pack_quantize(x, tok, *, wire_dtype: str = "f32",
                  bt: int = DEFAULT_BT, interpret: Optional[bool] = None):
    """x: [T, d] source rows; tok: [R] int32 slot→token map (−1 empty).
    Returns ``(q, scales)``: ``q`` [R, d] at the wire dtype (``[R,
    d_pad]`` for f8, padded to whole scale blocks), ``scales`` [R,
    d_pad/32] f32 for f8 else None — exactly
    :func:`repro.comm.dtypes.quantize_rows` of the packed rows."""
    if wire_dtype != "f8e4m3":
        out_dt = x.dtype if wire_dtype == "f32" else jnp.bfloat16
        return gather_rows(x, tok, out_dtype=out_dt, bt=bt,
                           interpret=interpret), None
    _, d = x.shape
    R = tok.shape[0]
    bt_ = block_rows(R, bt)
    d_pad = wdt.pad_to_block(d)
    if d_pad != d:
        x = jnp.pad(x, ((0, 0), (0, d_pad - d)))
    nb = d_pad // wdt.SCALE_BLOCK
    src, scratch = gather_specs(bt_, x)
    q, sct = pl.pallas_call(
        functools.partial(_pack_quant_kernel, block=wdt.SCALE_BLOCK),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(R // bt_, bt_), in_specs=[src],
            out_specs=[
                pl.BlockSpec((bt_, d_pad), lambda i, j, idx: (i, 0)),
                pl.BlockSpec((nb, bt_), lambda i, j, idx: (0, i)),
            ],
            scratch_shapes=scratch),
        out_shape=[out_struct((R, d_pad), wdt._f8_dtype(), x, tok),
                   out_struct((nb, R), jnp.float32, x, tok)],
        interpret=resolve_interpret(interpret),
    )(tok, tile_view(x))
    return q, sct.T
