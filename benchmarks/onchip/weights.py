"""Weights from a seed, made by the benchmark and not by the program.

Each function takes the architecture's layout module
(``layout/<reference>.py``, see ``harness.layout_module``), which names
the weights, their shapes and scales (``shapes``) and their paths in the
program's tree (``PROGRAM_PATHS``).

``canonical(layout, conf, key)`` gives the reference's own flat layout:
one array per kind of weight. ``to_program`` places the same arrays into
the parameter tree that ``repro.models`` builds, and ``check_layout``
refuses a program whose tree differs from that mapping, so a change of
the program's layout fails loudly instead of comparing the wrong
weights.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (wider than 32 bits
    too): the low 32 bits seed it and the rest is folded in."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    hi = seed >> 32
    while hi:
        key = jax.random.fold_in(key, hi & 0x7FFFFFFF)
        hi >>= 31
    return key


def canonical(layout, conf: dict, key, dtype=jnp.float32
              ) -> Dict[str, jnp.ndarray]:
    """All weights from ``key`` (trace inside ``jax.jit``), the i-th name
    in sorted order drawn from ``fold_in(key, i)``."""
    out = {}
    for i, (name, (shape, kind, std)) in enumerate(
            sorted(layout.shapes(conf).items())):
        if kind == "ones":
            out[name] = jnp.ones(shape, dtype)
        elif kind == "zeros":
            out[name] = jnp.zeros(shape, dtype)
        else:
            k = jax.random.fold_in(key, i)
            out[name] = (jax.random.normal(k, shape, jnp.float32) * std
                         ).astype(dtype)
    return out


def to_program(layout, canon: Dict[str, jnp.ndarray]):
    """The program's parameter tree holding the canonical arrays, each at
    its path in ``layout.PROGRAM_PATHS``. A whole number on a path is a
    position in a list (a period position of the scanned layers); the
    positions of one list are 0 to n - 1."""
    tree: dict = {}
    for name, arr in canon.items():
        *head, last = layout.PROGRAM_PATHS[name]
        node = tree
        for k in head:
            node = node.setdefault(k, {})
        node[last] = arr
    return _lists(tree)


def _lists(node):
    if not isinstance(node, dict):
        return node
    out = {k: _lists(v) for k, v in node.items()}
    pos = sorted(k for k in out if isinstance(k, int))
    if not pos:
        return out
    if len(pos) != len(out) or pos != list(range(len(pos))):
        raise ValueError(f"list positions {sorted(map(str, out))} are not "
                         f"0 to n - 1 alone")
    return [out[i] for i in pos]


def from_program(layout, tree) -> Dict[str, jnp.ndarray]:
    """Canonical arrays read back out of a program-layout tree."""
    out = {}
    for name, path in layout.PROGRAM_PATHS.items():
        node = tree
        try:
            for k in path:
                node = node[k]
        except (KeyError, IndexError):
            continue
        out[name] = node
    return out


def check_layout(layout, conf: dict, program_struct) -> None:
    """Raise unless the program's parameter tree is exactly the mapped
    canonical layout, leaf for leaf, shape for shape."""
    want = to_program(layout, {
        n: jax.ShapeDtypeStruct(s, jnp.float32)
        for n, (s, _, _) in layout.shapes(conf).items()})
    got_def = jax.tree.structure(program_struct)
    want_def = jax.tree.structure(want)
    if got_def != want_def:
        raise ValueError(f"program parameter tree {got_def} differs from "
                         f"the benchmark's layout {want_def}")
    for g, w in zip(jax.tree.leaves(program_struct), jax.tree.leaves(want)):
        if tuple(g.shape) != tuple(w.shape):
            raise ValueError(f"program leaf shape {g.shape} != {w.shape}")
