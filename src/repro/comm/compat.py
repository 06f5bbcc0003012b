"""Mesh and varying-manual-axes (vma) helpers for the comm subsystem.

Collectives run inside ``jax.shard_map``, whose type system tracks which
mesh axes a value varies over. The helpers here state the two casts the
MoE layer needs, plus the one mesh spelling every launcher shares.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """A mesh with Auto axis types: GSPMD shards everything outside the
    explicit ``shard_map`` regions (``jax.make_mesh`` defaults to
    Explicit). ``devices`` defaults to ``jax.devices()``; a described
    topology's devices give an ahead-of-time compile target."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def pvary_all(v, axes: Tuple[str, ...]):
    """Mark ``v`` varying over every axis in ``axes`` (value unchanged).

    Used where a value that is replicated by construction (e.g. the
    all-gathered plan-reuse signature) leaves through out_specs that
    treat it as per-device varying, and to give both arms of a
    ``lax.cond`` the same type."""
    missing = tuple(a for a in axes if a not in jax.typeof(v).vma)
    return jax.lax.pcast(v, missing, to="varying") if missing else v


def pmean_all(v, axes: Tuple[str, ...]):
    """pmean over all ``axes`` whatever the value's varying state: a
    value replicated over some of them must be cast to varying before a
    pmean that names them."""
    return jax.lax.pmean(pvary_all(v, axes), axes)
