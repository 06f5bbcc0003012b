"""Public jit'd wrappers for the Pallas kernels.

Each kernel resolves ``interpret`` by platform
(:func:`repro.kernels.resolve_interpret`): on CPU the kernel body runs
in the Pallas interpreter (correctness validation), on TPU the Mosaic
kernel is compiled. ``repro.kernels.ref`` holds the pure-jnp oracles
used by the allclose tests.
"""
from __future__ import annotations

from repro.kernels import condense as _condense
from repro.kernels import expert_ffn as _expert_ffn
from repro.kernels import similarity as _similarity
from repro.kernels import ref  # noqa: F401 (re-export for convenience)


def masked_similarity(x, mask, **kw):
    return _similarity.masked_similarity(x, mask, **kw)


def expert_ffn(h, w_up, w_gate, w_down, act="silu", **kw):
    act_name = act if isinstance(act, str) else \
        getattr(act, "__name__", "silu")
    return _expert_ffn.expert_ffn(h, w_up, w_gate, w_down,
                                  act_name=act_name, **kw)


def gather_rows(y, rep_idx, **kw):
    return _condense.gather_rows(y, rep_idx, **kw)


def pack_quantize(x, tok, **kw):
    from repro.kernels import pack as _pack
    return _pack.pack_quantize(x, tok, **kw)


def flash_attention(q, k, v, **kw):
    from repro.kernels import flash_attn as _fa
    return _fa.flash_attention(q, k, v, **kw)


def mamba_scan(dt, x, bmat, cmat, a, **kw):
    from repro.kernels import mamba_scan as _ms
    return _ms.mamba_scan(dt, x, bmat, cmat, a, **kw)
