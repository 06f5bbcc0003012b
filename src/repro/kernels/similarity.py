"""Blocked masked pairwise-similarity Pallas kernel (§V-A, TPU-adapted).

Pairwise cosine similarity over one condensation group is a rank-``d``
Gram matmul — exactly MXU work. The fast-measurement skip rules (same
expert / historical similarity) arrive as a boolean mask; whole output
tiles with no uncertain pair are skipped (tile-level early-out), which is
the TPU analogue of the paper's per-edge skipping (per-element control
flow is poison on a systolic array; tile granularity is free).

Grid: (G/bg, G/bg); each program computes one [bg, bg] tile of the Gram
matrix by streaming d in [bd]-sized VMEM slabs.

The mask arrives from a similarity *backend* (DESIGN.md §10,
``repro.condense.backends``): the "exact" backend passes the §V-A
uncertain mask; the "lsh" backend additionally restricts it to LSH
bucket collisions, which empties whole tiles and lets the early-out skip
them — :func:`mask_tile_fraction` reports exactly that win.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import out_struct, resolve_interpret


DEFAULT_BG = 128      # output tile edge (MXU-aligned)
DEFAULT_BD = 512      # feature-dim slab


def _sim_kernel(mask_any_ref, x_ref, y_ref, mask_ref, out_ref, *, bd, d):
    """One [bg,bg] output tile. x_ref/y_ref: [bg, d] row/col slabs in VMEM;
    mask_ref: [bg,bg] bool; mask_any_ref: [1,1] tile-level early-out flag
    (scalar prefetch)."""
    bg = out_ref.shape[0]

    @pl.when(mask_any_ref[0, 0] > 0)
    def compute():
        acc = jnp.zeros((bg, bg), jnp.float32)
        xx = jnp.zeros((bg,), jnp.float32)
        yy = jnp.zeros((bg,), jnp.float32)
        n_slabs = d // bd
        for s in range(n_slabs):
            xs = x_ref[:, s * bd:(s + 1) * bd].astype(jnp.float32)
            ys = y_ref[:, s * bd:(s + 1) * bd].astype(jnp.float32)
            acc += jax.lax.dot_general(
                xs, ys, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            xx += jnp.sum(xs * xs, axis=1)
            yy += jnp.sum(ys * ys, axis=1)
        inv = jax.lax.rsqrt(xx[:, None] * yy[None, :] + 1e-8)
        sim = (acc * inv + 1.0) * 0.5
        out_ref[...] = jnp.where(mask_ref[...], sim, 0.0)

    @pl.when(mask_any_ref[0, 0] == 0)
    def skip():
        out_ref[...] = jnp.zeros_like(out_ref)


def mask_tile_fraction(mask, bg: int = DEFAULT_BG) -> float:
    """Host-side: fraction of [bg, bg] output tiles with ≥1 True entry —
    the tiles the kernel must actually compute (everything else hits the
    early-out). The condensation-backend benchmark reports this per
    backend to show the LSH bucketing win at tile granularity."""
    import numpy as np
    m = np.asarray(mask)
    G = m.shape[-1]
    b = min(bg, G)
    if G % b:
        pad = b - G % b
        m = np.pad(m, [(0, 0)] * (m.ndim - 2) + [(0, pad), (0, pad)])
        G = m.shape[-1]
    nt = G // b
    tiles = m.reshape(m.shape[:-2] + (nt, b, nt, b)).any(axis=(-3, -1))
    return float(tiles.mean())


@functools.partial(jax.jit, static_argnames=("bg", "bd", "interpret"))
def masked_similarity(x, mask, *, bg: int = DEFAULT_BG,
                      bd: int = DEFAULT_BD,
                      interpret: Optional[bool] = None):
    """x: [G, d]; mask: [G, G] bool. Returns [G, G] f32 similarity in
    [0,1], zeroed where mask is False; fully-masked tiles are skipped.

    ``interpret=None`` resolves by platform
    (:func:`repro.kernels.resolve_interpret`)."""
    G, d = x.shape
    bg = min(bg, G)
    bd = min(bd, d)
    assert G % bg == 0
    if d % bd != 0:                      # pad features (zero rows are
        pad = bd - d % bd                # harmless for dot & norms)
        x = jnp.pad(x, ((0, 0), (0, pad)))
        d = x.shape[1]
    nt = G // bg
    # tile-level early-out flags, computed on the host side of the kernel
    mask_tiles = mask.reshape(nt, bg, nt, bg).any(axis=(1, 3))
    mask_any = mask_tiles.astype(jnp.int32)

    grid = (nt, nt)
    return pl.pallas_call(
        functools.partial(_sim_kernel, bd=bd, d=d),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (i, j)),          # mask_any
            pl.BlockSpec((bg, d), lambda i, j: (i, 0)),          # rows
            pl.BlockSpec((bg, d), lambda i, j: (j, 0)),          # cols
            pl.BlockSpec((bg, bg), lambda i, j: (i, j)),         # mask
        ],
        out_specs=pl.BlockSpec((bg, bg), lambda i, j: (i, j)),
        out_shape=out_struct((G, G), jnp.float32, x, mask),
        interpret=resolve_interpret(interpret),
    )(mask_any, x, x, mask)
