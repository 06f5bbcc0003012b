"""Expert-parallel MoE layer with LUFFY's two techniques (paper §III-§V).

Runs *inside* ``jax.shard_map`` over the full mesh: batch axes shard
sequences, the ``model`` axis shards experts. Per device this module sees

    x_local      [n_seq, S, d]     — this device's sequence slots
    experts      [E_local, ...]    — this device's expert shard

and performs: gate → (condense §V) → dispatch all-to-all → expert FFN →
(migrate §IV) combine all-to-all → un-condense. Since the plan/execute
split (DESIGN.md §7) the decisions live in a ``repro.plan.ExchangePlan``
and the data movement in ``repro.plan.execute_plan``; ``moe_core`` here
is the thin build + execute composition (plus the decode all-reduce
path, which has no all-to-all and bypasses the plan).

Key TPU adaptations (DESIGN.md §3):

* **Condensation** shrinks the *static* expert capacity ``C`` by the rate
  bucket; non-representative tokens take no dispatch slot, so the
  all-to-all operand itself is smaller.
* **Migration** is a bijection on global sequence slots, planned from the
  router output *before* dispatch (device-side Algorithm 1, replicated
  within each model row). The dispatch payload carries the *pre-norm*
  residual ``x``; expert devices compute ``norm→FFN→gate·y (+ residual on
  the primary copy)`` and address combine rows to the token's **new**
  home. The combine collective has the same operand size as vanilla —
  the migration win is the larger diagonal (local) fraction, which never
  crosses ICI links. Reported via the locality ledger in ``aux``.
* Capacity overflow drops rows exactly like GShard; primary (residual-
  carrying) rows are packed first so they survive longest. Drop rates are
  reported in ``aux``.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.comm import CommContext
from repro.config import LuffyConfig, MoEConfig, ModelConfig
from repro.core.gating import dispatch_positions, gate_apply, gate_init
# The exchange decision/execution machinery lives in repro.plan
# (DESIGN.md §7); these re-exports keep the historical import surface.
from repro.plan.exchange import (MoEAux, N_AUX, _exchange_sideband, _rms,
                                 build_exchange_plan, execute_plan,
                                 expert_ffn)

Array = jnp.ndarray


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def moe_init(key, cfg: ModelConfig):
    """Global expert stack [E, ...] (sharded over 'model' outside)."""
    from repro.models.blocks import dense_init, _dtype
    m = cfg.moe
    d, f, E = cfg.d_model, m.d_ff, m.num_experts
    pdt = _dtype(cfg.param_dtype)
    ks = jax.random.split(key, 5)
    scale_down = 1.0 / math.sqrt(2 * cfg.num_layers)
    p = {
        "router": gate_init(ks[0], d, E),
        "experts": {
            "w_up": (jax.random.normal(ks[1], (E, d, f)) / math.sqrt(d)).astype(pdt),
            "w_gate": (jax.random.normal(ks[2], (E, d, f)) / math.sqrt(d)).astype(pdt),
            "w_down": (jax.random.normal(ks[3], (E, f, d)) * scale_down
                       / math.sqrt(f)).astype(pdt),
        },
        "norm": {"scale": jnp.ones((d,), pdt)},
    }
    if m.num_shared_experts > 0:
        fs = f * m.num_shared_experts
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_up": (jax.random.normal(k1, (d, fs)) / math.sqrt(d)).astype(pdt),
            "w_gate": (jax.random.normal(k2, (d, fs)) / math.sqrt(d)).astype(pdt),
            "w_down": (jax.random.normal(k3, (fs, d)) * scale_down
                       / math.sqrt(fs)).astype(pdt),
        }
    return p


def capacity_for(moe: MoEConfig, tokens_local: int, num_experts: int,
                 rate: float = 0.0, slack: float = None) -> int:
    """Static per-(source, expert) capacity, condensation-bucket scaled."""
    cf = slack if slack is not None else moe.capacity_factor
    c = int(math.ceil(cf * tokens_local * moe.top_k * (1.0 - rate)
                      / num_experts))
    return max(8, ((c + 7) // 8) * 8)


def expert_ffn_2d(ew_local, h, act, cdt, fsdp_axes,
                  batch_sharded: bool = True):
    """Megatron-style expert FFN over the FSDP axes (decode path):

    weights are F-sharded (w_up/w_gate on dim 2, w_down on dim 1 — their
    stored layout, so NO weight resharding happens); the tiny decode
    activation rows are all-gathered, each rank computes its F-slice of
    the hidden, and the output partial-sums reduce-scatter back to each
    rank's own rows. Wire per layer ≈ 2×rows-size instead of the full
    expert weights (llama4 decode: ~20 MB vs ~2 GB; EXPERIMENTS.md §Perf).

    batch_sharded=False (long_500k: B=1 replicated over the fsdp axes):
    skip the gather/scatter — every rank holds the same rows, computes
    its F-slice partial, and a single psum yields the replicated output.
    """
    hc = h.astype(cdt)
    if batch_sharded:
        h_g = jax.lax.all_gather(hc, fsdp_axes, axis=1, tiled=True)
    else:
        h_g = hc
    up = jnp.einsum("erd,edf->erf", h_g, ew_local["w_up"].astype(cdt))
    gt = jnp.einsum("erd,edf->erf", h_g, ew_local["w_gate"].astype(cdt))
    hh = act(gt) * up                       # [E_l, R(_all), F_local]
    part = jnp.einsum("erf,efd->erd", hh,
                      ew_local["w_down"].astype(cdt))
    if batch_sharded:
        # reduce over F shards + scatter rows back to their owners
        return jax.lax.psum_scatter(part, fsdp_axes, scatter_dimension=1,
                                    tiled=True)
    return jax.lax.psum(part, fsdp_axes)


def moe_decode_allreduce(params, x, cfg: ModelConfig, *, capacity: int,
                         axis_name, use_kernel: bool = False,
                         fsdp_axes=None, batch_sharded: bool = True,
                         overlap: bool = False):
    """Decode-time expert parallelism via all-reduce (no all-to-all).

    At decode there is ONE token per sequence — the dispatch operand would
    be tiny and the token dim (S=1) cannot shard over the model axis. So
    tokens stay replicated across the model axis; each rank runs only its
    LOCAL experts on the tokens routed to them and the partial outputs are
    psum'd. Collective = one [B,1,d] all-reduce per layer.

    overlap (``LuffyConfig.exec_mode="decode_overlap"``, DESIGN.md §13):
    issue that combine psum CONCURRENTLY with the shared-expert FFN —
    the two are data-independent (the shared FFN reads the pre-expert
    hidden), so ``optimization_barrier`` ties the psum's consumption to
    the shared FFN and XLA's async collectives hide the wire time behind
    the matmuls. The value graph is unchanged
    (same operands, same addition order), so overlap is bit-identical
    to sync; with no shared experts or no mesh it degrades to sync.
    Returns (y, aux)."""
    from repro.models.blocks import _act, _dtype
    m = cfg.moe
    cdt = _dtype(cfg.compute_dtype)
    act = _act(cfg.act)
    n_seq, S, d = x.shape
    T = n_seq * S
    E = m.num_experts
    M = 1 if axis_name is None else jax.lax.axis_size(axis_name)
    E_local = E // M
    my = 0 if axis_name is None else jax.lax.axis_index(axis_name)
    C = capacity

    xf = x.reshape(T, d)
    xn = _rms(xf, params["norm"]["scale"]).astype(cdt)
    gate = gate_apply(params["router"], xn, m.top_k)
    lo = my * E_local
    local_e = gate.expert_idx - lo
    keep = (local_e >= 0) & (local_e < E_local)
    local_e = jnp.clip(local_e, 0, E_local - 1)
    pos = dispatch_positions(local_e, keep, E_local)
    valid = keep & (pos < C)
    e_safe = jnp.where(valid, local_e, 0).reshape(-1)
    p_safe = jnp.where(valid, pos, 0).reshape(-1)
    v_f = valid.reshape(-1)
    rows_in = jnp.zeros((E_local, C, d), cdt).at[e_safe, p_safe].add(
        jnp.tile(xn[:, None], (1, m.top_k, 1)).reshape(-1, d)
        * v_f[:, None].astype(cdt), mode="drop")
    if fsdp_axes:
        y_rows = expert_ffn_2d(params["experts"], rows_in, act, cdt,
                               fsdp_axes, batch_sharded=batch_sharded)
    else:
        y_rows = expert_ffn(params["experts"], rows_in, act, cdt,
                            use_kernel=use_kernel)
    vals = y_rows[e_safe, p_safe] * v_f[:, None].astype(cdt)
    vals = vals * gate.gate_weights.reshape(-1, 1).astype(cdt)
    delta = jnp.sum(vals.reshape(T, m.top_k, d), axis=1)
    sh = None
    if overlap and axis_name is not None and "shared" in params:
        from repro.models.blocks import ffn_apply
        # the psum may not be awaited before the shared FFN is done. Its
        # input is not tied to the expert partials: a barrier joins the
        # varying axes of its operands, so the model-replicated shared
        # output would be typed model-varying and the replicated out_spec
        # of the caller would no longer hold
        sh = ffn_apply(params["shared"], cfg,
                       _rms(x, params["norm"]["scale"]).astype(cdt))
        delta = jax.lax.psum(delta, axis_name)
        delta, sh = jax.lax.optimization_barrier((delta, sh))
    elif axis_name is not None:
        delta = jax.lax.psum(delta, axis_name)
    y = (xf + delta.astype(xf.dtype)).reshape(n_seq, S, d)
    if "shared" in params:
        if sh is None:
            from repro.models.blocks import ffn_apply
            sh = ffn_apply(params["shared"], cfg,
                           _rms(x, params["norm"]["scale"]).astype(cdt))
        y = y + sh.astype(y.dtype)
    kept = jnp.sum(keep.astype(jnp.float32))
    d_drop = 1.0 - jnp.sum(valid.astype(jnp.float32)) / jnp.maximum(kept, 1.0)
    z = jnp.float32(0.0)
    aux = MoEAux(gate.aux_loss, d_drop, z, z,
                 jnp.float32(1.0 / max(M, 1)),
                 *([z] * (N_AUX - 5)))
    return y, aux


# ---------------------------------------------------------------------------
# The per-device core
# ---------------------------------------------------------------------------

def moe_core_planned(params, x, sideband: Dict[str, Array],
                     cfg: ModelConfig, luffy: LuffyConfig, *, mode: str,
                     capacity: int, axis_name=None, threshold=None,
                     s_prev: Optional[Array] = None,
                     group_size: int = 128, combine_slack: float = 1.0,
                     use_kernel: bool = False,
                     comm: Optional[CommContext] = None,
                     reuse_from=None, condense_reuse_from=None,
                     plan_template=None, wire_ef: Optional[Array] = None):
    """``moe_core`` that also returns the :class:`ExchangePlan` it built
    — the plan-lifecycle entry point (DESIGN.md §9). ``reuse_from``
    threads a prior plan/signature into ``build_exchange_plan``'s
    revalidation fast path; ``condense_reuse_from`` (a
    :class:`repro.condense.CondenseCarry`) does the same for the
    condensation map (DESIGN.md §10); ``plan_template`` (a cached static
    template from :class:`repro.plan.cache.PlanCache`) switches the
    vanilla path to ``instantiate_plan``, skipping planning entirely;
    ``wire_ef`` threads the lossy-wire error-feedback residual
    (DESIGN.md §15) into the executor.
    Returns (y, new_sideband, s_next, aux, plan, cond_carry, wire_ef)."""
    from repro.models.blocks import _dtype
    from repro.plan.exchange import instantiate_decode_plan, instantiate_plan
    comm = CommContext.ensure(comm, axis_name)
    n_seq, S, d = x.shape
    xf = x.reshape(n_seq * S, d)
    xn = _rms(xf, params["norm"]["scale"]).astype(_dtype(cfg.compute_dtype))
    from repro.obs import trace as obs_trace
    with obs_trace.phase("router"):
        gate = gate_apply(params["router"], xn, cfg.moe.top_k)
    with obs_trace.phase("plan_build") as _sp:
        if plan_template is not None:
            inst = (instantiate_decode_plan if plan_template.mode == "decode"
                    else instantiate_plan)
            plan = inst(
                plan_template, gate, xn, cfg, comm, capacity=capacity,
                sideband=sideband, use_kernel=use_kernel)
        else:
            plan = build_exchange_plan(
                gate, xn, cfg, luffy, comm, mode=mode, capacity=capacity,
                sideband=sideband, threshold=threshold, s_prev=s_prev,
                group_size=group_size, combine_slack=combine_slack,
                use_kernel=use_kernel, reuse_from=reuse_from,
                condense_reuse_from=condense_reuse_from)
        plan = _sp.fence(plan)
    with obs_trace.phase("exchange") as _sp:
        y, aux = execute_plan(params, x, sideband, plan, cfg,
                              wire_ef=wire_ef)
        y = _sp.fence(y)
    return (y, aux.sideband, aux.s_next, aux.moe, plan, aux.cond_carry,
            aux.wire_ef)


def moe_core(params, x, sideband: Dict[str, Array], cfg: ModelConfig,
             luffy: LuffyConfig, *, mode: str, capacity: int,
             axis_name=None, threshold=None,
             s_prev: Optional[Array] = None,
             group_size: int = 128, combine_slack: float = 1.0,
             use_kernel: bool = False,
             comm: Optional[CommContext] = None
             ) -> Tuple[Array, Dict[str, Array], Optional[Array], MoEAux]:
    """One MoE sublayer on this device's shard: build + execute.

    x: [n_seq, S, d] pre-norm hidden. sideband: {"labels":[n_seq,S],
    "seq_len":[n_seq]} — travels with sequences under migration.
    mode: "vanilla" | "migrate". Condensation is on iff s_prev is not None
    or luffy.enable_condensation and mode != decode-style call.
    comm: collective strategy + topology (repro.comm); the historical
    ``(comm=None, axis_name=...)`` spelling is normalized to a flat
    context here, at the call boundary — downstream the executor holds
    exactly one non-optional comm handle (``CommContext.ensure``).
    Returns (y, new_sideband, s_next, aux). In vanilla mode
    ``y = x + moe_delta``; in migrate mode ``y`` is the full post-block
    hidden materialized at *new* slots.

    This is nothing but the two-phase ``repro.plan`` API (DESIGN.md §7):
    every decision lives in the :class:`~repro.plan.ExchangePlan`, every
    byte moves in :func:`~repro.plan.execute_plan`. (The plan-lifecycle
    sibling ``moe_core_planned`` additionally returns the plan and takes
    ``reuse_from``/``plan_template``; this historical entry point keeps
    the 4-tuple contract.)
    """
    y, sb, s_next, aux, _, _, _ = moe_core_planned(
        params, x, sideband, cfg, luffy, mode=mode, capacity=capacity,
        axis_name=axis_name, threshold=threshold, s_prev=s_prev,
        group_size=group_size, combine_slack=combine_slack,
        use_kernel=use_kernel, comm=comm)
    return y, sb, s_next, aux
