"""The layout of the MoE decoder that ``reference/moe_decoder.py``
computes: every layer attention + gated MoE over all experts, stacked in
one scanned group. Everything the harness, ``weights.py`` and
``flops.py`` need to know of this architecture is here:

* ``SIZE_KEYS``, ``program_sizes`` and ``program_config``: which keys of
  a configuration file are sizes of the program's ``ModelConfig``, and
  the config the program runs for a file;
* ``shapes``: the canonical weights, each with its shape and scale;
* ``PROGRAM_PATHS``: where each canonical weight sits in the program's
  parameter tree;
* ``forward_flops_per_token``: the model FLOPs of one token's forward
  pass on this chip (``flops.py`` states what is counted).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import numpy as np

# config-file key -> how the program's ModelConfig holds it
SIZE_KEYS = ("num_layers", "d_model", "num_heads", "head_dim", "vocab_size",
             "num_experts", "top_k", "expert_d_ff", "capacity_factor",
             "router_aux_coef", "use_rope", "tie_embeddings", "norm", "act",
             "param_dtype", "compute_dtype")


def program_sizes(base) -> dict:
    """The registry's ``ModelConfig`` under each of ``SIZE_KEYS``."""
    return {"num_layers": base.num_layers, "d_model": base.d_model,
            "num_heads": base.attn.num_heads, "head_dim": base.attn.head_dim,
            "vocab_size": base.vocab_size,
            "num_experts": base.moe.num_experts, "top_k": base.moe.top_k,
            "expert_d_ff": base.moe.d_ff,
            "capacity_factor": base.moe.capacity_factor,
            "router_aux_coef": base.moe.router_aux_coef,
            "use_rope": base.attn.use_rope,
            "tie_embeddings": base.tie_embeddings, "norm": base.norm,
            "act": base.act, "param_dtype": base.param_dtype,
            "compute_dtype": base.compute_dtype}


def program_config(base, conf: dict):
    """The registry's ``base`` with the file's sizes. Keys, queries and
    values have the same number of heads."""
    if not conf["gated_experts"]:
        raise ValueError("the program builds every expert gated; a "
                         "configuration of ungated experts cannot run")
    attn = dataclasses.replace(
        base.attn, num_heads=conf["num_heads"],
        num_kv_heads=conf["num_heads"], head_dim=conf["head_dim"],
        use_rope=conf["use_rope"])
    moe = dataclasses.replace(
        base.moe, num_experts=conf["num_experts"], top_k=conf["top_k"],
        d_ff=conf["expert_d_ff"], capacity_factor=conf["capacity_factor"],
        router_aux_coef=conf["router_aux_coef"])
    return dataclasses.replace(
        base, num_layers=conf["num_layers"], d_model=conf["d_model"],
        vocab_size=conf["vocab_size"], attn=attn, moe=moe,
        tie_embeddings=conf["tie_embeddings"], norm=conf["norm"],
        act=conf["act"], param_dtype=conf["param_dtype"],
        compute_dtype=conf["compute_dtype"])


def shapes(conf: dict) -> Dict[str, Tuple[Tuple[int, ...], str, float]]:
    """Canonical name -> (shape, kind, std); kind is normal/ones/zeros.
    Scales follow the program's own initialisers."""
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


# canonical name -> path in the program's tree (layers scanned as one
# stacked group, period position 0, since every layer is attention + MoE)
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


def _path(p) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in p)


def forward_flops_per_token(struct, conf: dict, lengths) -> float:
    """``struct``: the program's parameter tree (ShapeDtypeStructs);
    ``lengths``: the non-padding lengths of the sequences trained. All
    experts are on this chip; each token reaches ``top_k`` of them."""
    import jax
    L, k = conf["num_layers"], conf["top_k"]
    attn = experts = router = head = 0.0
    for path, leaf in jax.tree_util.tree_leaves_with_path(struct):
        name = _path(path)
        shape = tuple(leaf.shape)
        if "/attn/" in name:
            attn += 2.0 * np.prod(shape) / L          # per layer
        elif "/experts/" in name:
            # [L, E, a, b]: one matrix per expert per layer
            experts += 2.0 * shape[2] * shape[3] * k
        elif "/router/" in name:
            router += 2.0 * np.prod(shape) / L
        elif name in ("embed/table", "unembed/w") and (
                name == "unembed/w" or conf["tie_embeddings"]):
            head += 2.0 * np.prod(shape)
    lens = np.asarray(lengths, np.float64)
    # mean causal context of a non-padding token: (len + 1) / 2 weighted
    ctx = float(np.sum(lens * (lens + 1) / 2) / np.sum(lens))
    width = conf["num_heads"] * conf["head_dim"]
    scores = 2.0 * 2.0 * ctx * width                  # QK^T and PV
    return L * (attn + scores + router + experts) + head
