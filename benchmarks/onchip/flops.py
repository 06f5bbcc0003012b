"""Model FLOPs per trained token, from the parameter tree the program
builds (shapes only) and the traffic's lengths. The architecture's
layout module (``layout/<reference>.py``) counts one token's forward
pass in ``forward_flops_per_token``; every layout keeps to this
contract.

Counted: the matrix products of the forward pass of the work this
chip's share of the model does, times three for forward and backward.
Attention projections; the attention scores and their weighted sum over
the causal context of each non-padding token; the router; the routed
experts, top-k a token, in the share of the experts this chip holds,
with as many matrices as the tree holds per expert; a shared expert or
a dense FFN, where the architecture has one; the LM head. Not counted:
capacity padding, recomputation (remat), padded positions, norms and
element-wise work.
"""
from __future__ import annotations


def train_flops_per_token(layout, struct, conf: dict, lengths) -> float:
    """``struct``: the program's parameter tree (ShapeDtypeStructs);
    ``lengths``: the non-padding lengths of the sequences trained."""
    return 3.0 * layout.forward_flops_per_token(struct, conf, lengths)
