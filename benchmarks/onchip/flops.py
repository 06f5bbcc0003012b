"""Model FLOPs per trained token, from the parameter tree the program
builds (shapes only) and the traffic's lengths.

Counted: the matrix products of the forward pass, times three for
forward and backward. Attention projections; the attention scores and
their weighted sum over the causal context of each non-padding token;
the router; the routed experts, top-k of them, with as many matrices as
the tree holds per expert; the LM head. Not counted: capacity padding,
recomputation (remat), padded positions, norms and element-wise work.
"""
from __future__ import annotations

import numpy as np


def _path(p) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in p)


def forward_flops_per_token(struct, conf: dict, lengths) -> float:
    """``struct``: the program's parameter tree (ShapeDtypeStructs);
    ``lengths``: the non-padding lengths of the sequences trained."""
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


def train_flops_per_token(struct, conf: dict, lengths) -> float:
    return 3.0 * forward_flops_per_token(struct, conf, lengths)
