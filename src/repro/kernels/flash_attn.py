"""Flash-attention Pallas kernel (streaming softmax in VMEM).

The roofline (§Roofline) shows every training/prefill shape memory-bound,
with attention's [B,H,Sq,Sk] score tensor a top HBM consumer — exactly
the traffic FlashAttention (paper ref [29]) eliminates. This kernel keeps
one (bq × bk) score tile in VMEM with running (m, l, acc) statistics.

Grid: (B·H, Sq/bq, Sk/bk); the k axis is the reduction — (m, l, acc)
accumulate in VMEM scratch across k steps (TPU grids iterate the last
axis innermost, sequentially per core).

Supports causal + sliding-window masks via position arithmetic; fully
masked tiles exit early (the same tile-level skip the similarity kernel
uses — and the band-slicing done at the jnp level in attend_chunked).
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


DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale, causal, window, bq, bk, nk):
    """q/o: [1, bq, hd]; k/v: [1, bk, hd]; scratch m/l: [bq, 1] and
    acc: [bq, hd] f32, carried across the k grid axis."""
    kk = pl.program_id(2)
    qq = pl.program_id(1)

    @pl.when(kk == 0)
    def init():
        m_ref[...] = jnp.full(m_ref.shape, NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    q0 = qq * bq
    k0 = kk * bk
    # tile-level early-out: causal tiles fully in the future, window
    # tiles fully in the past
    live = jnp.bool_(True)
    if causal:
        live = live & (k0 <= q0 + bq - 1)
    if window is not None:
        live = live & ((k0 + bk - 1) >= (q0 - window + 1))

    @pl.when(live)
    def compute():
        q = q_ref[0].astype(jnp.float32)            # [bq, hd]
        k = k_ref[0].astype(jnp.float32)            # [bk, hd]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qp = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kp = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), bool)
        if causal:
            mask &= kp <= qp
        if window is not None:
            mask &= (qp - kp) < window
        s = jnp.where(mask, s, NEG)
        m_prev = m_ref[...]                          # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        m_new = jnp.maximum(m_new, -0.5e30)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kk == nk - 1)
    def finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "scale", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    scale=None, bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                    interpret: Optional[bool] = None):
    """q: [B,S,H,hd]; k,v: [B,S,KV,hd] (KV heads pre-expanded or == H).
    Returns [B,S,H,hd]."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    assert KV == H, "expand GQA kv heads before the kernel"
    scale = scale or 1.0 / math.sqrt(hd)
    bq_, bk_ = min(bq, S), min(bk, S)
    assert S % bq_ == 0 and S % bk_ == 0
    nq, nk = S // bq_, S // bk_
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          window=window, bq=bq_, bk=bk_, nk=nk),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq_, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk_, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk_, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq_, hd), lambda b, i, j: (b, i, 0)),
        out_shape=out_struct((B * H, S, hd), q.dtype, qf, kf, vf),
        scratch_shapes=[pltpu.VMEM((bq_, 1), jnp.float32),
                        pltpu.VMEM((bq_, 1), jnp.float32),
                        pltpu.VMEM((bq_, hd), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(qf, kf, vf)
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
