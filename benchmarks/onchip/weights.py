"""Weights from a seed, made by the benchmark and not by the program.

``canonical(conf, seed)`` gives the reference's own flat layout: one
array per kind of weight, stacked over layers. ``to_program`` places the
same arrays into the parameter tree that ``repro.models`` builds, and
``check_layout`` refuses a program whose tree differs from that mapping,
so a change of the program's layout fails loudly instead of comparing
the wrong weights. Scales follow the program's own initialisers.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp


def shapes(conf: dict) -> Dict[str, Tuple[Tuple[int, ...], str, float]]:
    """Canonical name -> (shape, kind, std); kind is normal/ones/zeros."""
    L, d, V = conf["num_layers"], conf["d_model"], conf["vocab_size"]
    q = conf["num_heads"] * conf["head_dim"]
    E, f = conf["num_experts"], conf["expert_d_ff"]
    down = 1.0 / math.sqrt(2 * L)
    out = {
        "embed": ((V, d), "normal", 0.02),
        "final_norm.scale": ((d,), "ones", 0.0),
        "attn_norm.scale": ((L, d), "ones", 0.0),
        "wq": ((L, d, q), "normal", 1.0 / math.sqrt(d)),
        "wk": ((L, d, q), "normal", 1.0 / math.sqrt(d)),
        "wv": ((L, d, q), "normal", 1.0 / math.sqrt(d)),
        "wo": ((L, q, d), "normal", down / math.sqrt(q)),
        "moe_norm.scale": ((L, d), "ones", 0.0),
        "router": ((L, d, E), "normal", 1.0 / math.sqrt(d)),
        "w_up": ((L, E, d, f), "normal", 1.0 / math.sqrt(d)),
        "w_gate": ((L, E, d, f), "normal", 1.0 / math.sqrt(d)),
        "w_down": ((L, E, f, d), "normal", down / math.sqrt(f)),
    }
    if conf["norm"] == "ln":
        out["final_norm.bias"] = ((d,), "zeros", 0.0)
        out["attn_norm.bias"] = ((L, d), "zeros", 0.0)
    if not conf["tie_embeddings"]:
        out["unembed"] = ((d, V), "normal", 1.0 / math.sqrt(d))
    return out


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


def canonical(conf: dict, key, dtype=jnp.float32) -> Dict[str, jnp.ndarray]:
    """All weights from ``key`` (trace inside ``jax.jit``)."""
    out = {}
    for i, (name, (shape, kind, std)) in enumerate(
            sorted(shapes(conf).items())):
        if kind == "ones":
            out[name] = jnp.ones(shape, dtype)
        elif kind == "zeros":
            out[name] = jnp.zeros(shape, dtype)
        else:
            k = jax.random.fold_in(key, i)
            out[name] = (jax.random.normal(k, shape, jnp.float32) * std
                         ).astype(dtype)
    return out


# canonical name -> path in the program's tree (layers scanned as one
# stacked group, since every layer of these models is attention + MoE)
PROGRAM_PATHS = {
    "embed": ("embed", "table"),
    "unembed": ("unembed", "w"),
    "final_norm.scale": ("final_norm", "scale"),
    "final_norm.bias": ("final_norm", "bias"),
    "attn_norm.scale": ("layers", 0, "attn_norm", "scale"),
    "attn_norm.bias": ("layers", 0, "attn_norm", "bias"),
    "wq": ("layers", 0, "attn", "wq"),
    "wk": ("layers", 0, "attn", "wk"),
    "wv": ("layers", 0, "attn", "wv"),
    "wo": ("layers", 0, "attn", "wo"),
    "moe_norm.scale": ("layers", 0, "moe", "norm", "scale"),
    "router": ("layers", 0, "moe", "router", "w_gate"),
    "w_up": ("layers", 0, "moe", "experts", "w_up"),
    "w_gate": ("layers", 0, "moe", "experts", "w_gate"),
    "w_down": ("layers", 0, "moe", "experts", "w_down"),
}


def to_program(canon: Dict[str, jnp.ndarray]):
    """The program's parameter tree holding the canonical arrays."""
    tree: dict = {}
    for name, arr in canon.items():
        node = tree
        path = PROGRAM_PATHS[name]
        for k in path[:-1]:
            if k == 0:
                node = node.setdefault("_list", {})
                continue
            node = node.setdefault(k, {})
        node[path[-1]] = arr

    def fix(node):
        if isinstance(node, dict):
            if "_list" in node:
                inner = {k: fix(v) for k, v in node.items() if k != "_list"}
                assert not inner, inner
                return [fix(node["_list"])]
            return {k: fix(v) for k, v in node.items()}
        return node

    return fix(tree)


def from_program(tree) -> Dict[str, jnp.ndarray]:
    """Canonical arrays read back out of a program-layout tree."""
    out = {}
    for name, path in PROGRAM_PATHS.items():
        node = tree
        try:
            for k in path:
                node = node[k]
        except (KeyError, IndexError):
            continue
        out[name] = node
    return out


def check_layout(conf: dict, program_struct) -> None:
    """Raise unless the program's parameter tree is exactly the mapped
    canonical layout, leaf for leaf, shape for shape."""
    want = to_program({n: jax.ShapeDtypeStruct(s, jnp.float32)
                       for n, (s, _, _) in shapes(conf).items()})
    got_def = jax.tree.structure(program_struct)
    want_def = jax.tree.structure(want)
    if got_def != want_def:
        raise ValueError(f"program parameter tree {got_def} differs from "
                         f"the benchmark's layout {want_def}")
    for g, w in zip(jax.tree.leaves(program_struct), jax.tree.leaves(want)):
        if tuple(g.shape) != tuple(w.shape):
            raise ValueError(f"program leaf shape {g.shape} != {w.shape}")
