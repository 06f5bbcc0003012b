"""Grouped expert-FFN Pallas kernel: the MoE compute hot-spot (§VII-C —
token condensation's computation saving materializes here, as fewer rows).

Computes ``out[e] = (act(h[e] @ w_gate[e]) * (h[e] @ w_up[e])) @ w_down[e]``
for every local expert. Grid: (E_local, R/br, F/bf); the f-dim is the
reduction for the second matmul, so each (e, r) accumulates over the f
grid axis into the output tile — BlockSpecs keep one [br, bf] activation
slab and one [bf, d] w_down slab in VMEM at a time.
"""
from __future__ import annotations

import functools

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import out_struct, resolve_interpret


DEFAULT_BR = 128     # rows per tile (tokens)
DEFAULT_BF = 512     # expert-hidden slab


def _ffn_kernel(h_ref, wu_ref, wg_ref, wd_ref, out_ref, *, act_name):
    """h: [br, d]; wu/wg: [d, bf]; wd: [bf, d]; out: [br, d] (accumulated
    over the f grid axis)."""
    f_idx = pl.program_id(2)
    act = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[act_name]
    h = h_ref[0].astype(jnp.float32)                       # [br, d]
    up = jax.lax.dot_general(h, wu_ref[0].astype(jnp.float32),
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    gt = jax.lax.dot_general(h, wg_ref[0].astype(jnp.float32),
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    part = jax.lax.dot_general(act(gt) * up,
                               wd_ref[0].astype(jnp.float32),
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)

    @pl.when(f_idx == 0)
    def init():
        out_ref[0] = part.astype(out_ref.dtype)

    @pl.when(f_idx > 0)
    def accum():
        out_ref[0] = (out_ref[0].astype(jnp.float32)
                      + part).astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("act_name", "br", "bf", "interpret"))
def expert_ffn(h, w_up, w_gate, w_down, act_name: str = "silu", *,
               br: int = DEFAULT_BR, bf: int = DEFAULT_BF,
               interpret: Optional[bool] = None):
    """h: [E, R, d]; w_up/w_gate: [E, d, F]; w_down: [E, F, d]."""
    E, R, d = h.shape
    F = w_up.shape[-1]
    br_ = min(br, R)
    bf_ = min(bf, F)
    assert R % br_ == 0 and F % bf_ == 0, (R, br_, F, bf_)
    grid = (E, R // br_, F // bf_)
    return pl.pallas_call(
        functools.partial(_ffn_kernel, act_name=act_name),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, br_, d), lambda e, r, f: (e, r, 0)),
            pl.BlockSpec((1, d, bf_), lambda e, r, f: (e, 0, f)),
            pl.BlockSpec((1, d, bf_), lambda e, r, f: (e, 0, f)),
            pl.BlockSpec((1, bf_, d), lambda e, r, f: (e, f, 0)),
        ],
        out_specs=pl.BlockSpec((1, br_, d), lambda e, r, f: (e, r, 0)),
        out_shape=out_struct((E, R, d), h.dtype, h, w_up, w_gate, w_down),
        interpret=resolve_interpret(interpret),
    )(h, w_up, w_gate, w_down)
