"""Pallas kernels for the MoE hot spots, with pure-jnp oracles in
:mod:`repro.kernels.ref` and thin wrappers in :mod:`repro.kernels.ops`."""
from __future__ import annotations

import jax


def resolve_interpret(interpret=None):
    """How a kernel runs: an explicit value wins; otherwise the TPU
    interpreter on the CPU (``pltpu.InterpretParams``: it models DMAs
    and semaphores, and types its loops inside ``shard_map``), False on
    the TPU (the compiled Mosaic kernel), and an error on any other
    platform."""
    if interpret is not None:
        return interpret
    platform = jax.default_backend()
    if platform == "cpu":
        from jax.experimental.pallas import tpu as pltpu
        return pltpu.InterpretParams()
    if platform == "tpu":
        return False
    raise RuntimeError(f"no Pallas kernel path for platform {platform!r}")


def out_struct(shape, dtype, *inputs) -> jax.ShapeDtypeStruct:
    """A kernel's output type: inside ``shard_map`` it varies over every
    mesh axis that any of ``inputs`` varies over (empty outside)."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in inputs))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
