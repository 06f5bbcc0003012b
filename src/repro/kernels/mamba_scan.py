"""Fused chunked Mamba-scan Pallas kernel.

EXPERIMENTS.md §Perf H4 showed that `lax.scan` unrolling does NOT fix the
SSM memory term: the [bd, N] state still round-trips HBM every token.
This kernel is the real fix — the Mamba-kernel insight on TPU:

* grid (B, d_inner/bd, S/bs), with the sequence axis innermost
  (sequential); the running state h [N, bd] lives in VMEM scratch for
  the whole sequence and never touches HBM;
* the per-step tensors da = exp(dt·A) and dbx = dt·x·B are fused in
  VMEM — the [B,S,di,N] intermediates of the jnp path (6.7 GB/seq at
  32k for hymba) are never materialized.

HBM traffic per chunk ≈ inputs (dt, x, B, C tiles) + y tile:
~(3·bs·bd + 2·bs·N) floats vs the naive scan's
~3·bs·bd·N — a ×N/~16 reduction for hymba's N=16.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import out_struct, resolve_interpret


DEFAULT_BD = 256     # d_inner tile
DEFAULT_BS = 256     # sequence chunk


def _mamba_kernel(dt_ref, x_ref, bt_ref, ct_ref, at_ref, y_ref, h_ref, *,
                  bs):
    """dt/x/y: [1, bs, bd]; bt/ct: [1, N, bs] (B and C transposed);
    at: [N, bd]; h: [N, bd] f32 scratch carried across the sequence grid
    axis. The state keeps d_inner on lanes, so every per-step tensor is
    a [1, bd] row or an [N, bd] tile."""
    @pl.when(pl.program_id(2) == 0)
    def init():
        h_ref[...] = jnp.zeros(h_ref.shape, jnp.float32)

    a = at_ref[...].astype(jnp.float32)                # [N, bd]
    bm = bt_ref[0].astype(jnp.float32)                 # [N, bs]
    cm = ct_ref[0].astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, bm.shape, 1)

    def step(i, h):
        dt_i = dt_ref[0, pl.ds(i, 1), :].astype(jnp.float32)   # [1, bd]
        x_i = x_ref[0, pl.ds(i, 1), :].astype(jnp.float32)
        sel = lane == i
        b_i = jnp.sum(jnp.where(sel, bm, 0.0), axis=1, keepdims=True)
        c_i = jnp.sum(jnp.where(sel, cm, 0.0), axis=1, keepdims=True)
        h = jnp.exp(dt_i * a) * h + (dt_i * x_i) * b_i          # [N, bd]
        y_ref[0, pl.ds(i, 1), :] = jnp.sum(
            h * c_i, axis=0, keepdims=True).astype(y_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, bs, step, h_ref[...])


@functools.partial(jax.jit, static_argnames=("bd", "bs", "interpret"))
def mamba_scan(dt, x, bmat, cmat, a, *, bd: int = DEFAULT_BD,
               bs: int = DEFAULT_BS, interpret: Optional[bool] = None):
    """Fused selective-SSM scan.

    dt:   [B, S, di]  (post-softplus step sizes)
    x:    [B, S, di]  (post-conv, post-silu inputs)
    bmat: [B, S, N]   (input gate)
    cmat: [B, S, N]   (output gate)
    a:    [di, N]     (negative continuous-time decay, -exp(a_log))
    Returns y [B, S, di] = C_t · h_t with h_t = exp(dt·a)·h + dt·x·B_t.
    """
    B, S, di = dt.shape
    N = bmat.shape[-1]
    bd_, bs_ = min(bd, di), min(bs, S)
    assert di % bd_ == 0 and S % bs_ == 0
    return pl.pallas_call(
        functools.partial(_mamba_kernel, bs=bs_),
        grid=(B, di // bd_, S // bs_),
        in_specs=[
            pl.BlockSpec((1, bs_, bd_), lambda b, d, s: (b, s, d)),  # dt
            pl.BlockSpec((1, bs_, bd_), lambda b, d, s: (b, s, d)),  # x
            pl.BlockSpec((1, N, bs_), lambda b, d, s: (b, 0, s)),    # B^T
            pl.BlockSpec((1, N, bs_), lambda b, d, s: (b, 0, s)),    # C^T
            pl.BlockSpec((N, bd_), lambda b, d, s: (0, d)),          # a^T
        ],
        out_specs=pl.BlockSpec((1, bs_, bd_), lambda b, d, s: (b, s, d)),
        out_shape=out_struct((B, S, di), dt.dtype, dt, x, bmat, cmat, a),
        scratch_shapes=[pltpu.VMEM((N, bd_), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(dt, x, bmat.transpose(0, 2, 1), cmat.transpose(0, 2, 1), a.T)
