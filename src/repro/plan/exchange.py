"""Plan/execute split for the MoE exchange (DESIGN.md §7).

Every decision about one expert-parallel exchange — routing, the
condensation map (§V), the migration assignment (§IV), the pipeline
chunk schedule (§6) and the per-phase cost estimates — is materialized
as ONE frozen record, :class:`ExchangePlan`, by
:func:`build_exchange_plan`; :func:`execute_plan` is a thin executor
that moves the bytes the plan prescribes. ``core/moe_layer.moe_core``
is build + execute and nothing else, so the train forward, the serving
prefill path and any future consumer share the same decisions and the
same executor, and planning policy (``LuffyConfig.plan_objective``,
:mod:`repro.plan.objectives`) is swappable without touching execution.

Both halves run *inside* the same ``shard_map`` trace: the plan's array
fields are per-device traced values (replicated where they must agree,
e.g. the migration permutation), its static fields (mode, capacity,
chunk schedule, comm context, estimates) are fixed at trace time.
Splitting a pure computation into two functions does not change any
value's defining subgraph, so build + execute is bit-identical to the
fused pre-split ``moe_core`` (tested: ``tests/test_plan.py``).

Plan lifecycle (DESIGN.md §9): plans are also *reused*. Inside a layer
scan, :func:`build_exchange_plan` takes ``reuse_from`` (a prior plan or
its :class:`PlanSignature`) and, under ``LuffyConfig.plan_reuse``,
revalidates the carried decision with a cheap routing-signature compare
instead of re-running the migration greedy; on the serving path,
:func:`instantiate_plan` binds fresh routing onto a cached static
template (``repro.plan.cache``) without any planning at all.
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from repro.comm import CommContext
from repro.comm import dtypes as wdt
from repro.comm import ledger as comm_ledger
from repro.condense import plan as cplan
from repro.condense import wire as cwire
from repro.condense.plan import (CondenseCarry, CondensePlan,
                                 identity_condense_plan, uncondense)
from repro.config import LuffyConfig, ModelConfig
from repro.core import migration as mig
from repro.core.gating import GateOutput, dispatch_positions
from repro.obs import trace as obs_trace
from repro.plan import objectives
from repro.plan.estimate import PlanEstimate, estimate_exchange
from repro.sched import (ChunkPlan, plan_chunks, plan_unique_chunks,
                         run_pipeline)
from repro.sched.cost import resolve_chunk_overhead_ms

Array = jnp.ndarray

# Fallback chunk count when the objective-planned search has no topology
# to price against (mirrors the historical --pipeline-chunks default).
DEFAULT_PIPELINE_CHUNKS = 4

# Trace-time planning-call counter: incremented once per
# build_exchange_plan call. The serving cache's zero-planning guarantee
# is asserted against it (a warm PlanCache prefill must not move it).
BUILD_CALLS = 0


class MoEAux(NamedTuple):
    aux_loss: Array
    dispatch_drop: Array      # fraction of kept rows dropped at dispatch
    combine_drop: Array       # fraction of rows dropped at combine regroup
    condense_rate: Array      # fraction of tokens condensed
    local_frac: Array         # fraction of combine rows staying on-device
    traffic_before: Array     # plan ledger (link-cost-weighted tokens
    traffic_after: Array      # crossing devices, without/with migration)
    inter_bytes_flat: Array   # dispatch bytes a flat a2a ships across nodes
    inter_bytes_dedup: Array  # modeled bytes after per-node dedup (what
                              # the hier dedup wire ships, in every mode)
    plans_built: Array        # plan-reuse ledger (DESIGN.md §9): 1 when
    plans_reused: Array       # the full migration planner ran / when a
    reuse_mismatch: Array     # carried plan revalidated / when a carried
                              # plan FAILED revalidation (and was rebuilt)
    measured_pairs: Array     # condensation ledger (DESIGN.md §10): pairs
                              # the similarity backend actually measured
    condense_built: Array     # 1 when the similarity build ran / when a
    condense_reused: Array    # carried condense plan was reused instead
    inter_bytes_shipped: Array  # bytes the dedup wire ACTUALLY shipped
                                # across nodes (0 on the dense wire);
                                # equals inter_bytes_dedup when active

N_AUX = len(MoEAux._fields)


class PlanSignature(NamedTuple):
    """Routing signature a carried plan revalidates against.

    ``counts``/``lens`` are the migration planner's inputs *expected at
    the next exchange* — the gathered per-(global slot, device) expert
    counts and sequence lengths, rows permuted into the post-migration
    slot layout (``next_signature``). The greedy is deterministic in
    these inputs, so observed == expected implies the planner would keep
    every sequence at its current home and the greedy can be skipped
    (``repro.core.migration.home_plan``). ``valid`` > 0.5 marks that a
    plan was actually built (the first MoE sublayer seeds it).
    """
    counts: Array             # [n_slots, M] f32 expected planner counts
    lens: Array               # [n_slots] f32 expected sequence lengths
    valid: Array              # [] f32 — 1.0 once a plan has been built


def routing_signature_matches(sig: PlanSignature, counts, lens):
    """Cheap revalidation: observed planner inputs == expected. numpy in
    -> host bool, jnp in -> traced bool (both backends share this exact
    predicate; ``benchmarks/fig_plan_reuse.py`` drives the host side)."""
    if (tuple(sig.counts.shape) != tuple(counts.shape)
            or tuple(sig.lens.shape) != tuple(lens.shape)):
        return (jnp.bool_(False) if isinstance(counts, jnp.ndarray)
                else False)
    xp = jnp if isinstance(counts, jnp.ndarray) else np
    same = xp.all(sig.counts == counts) & xp.all(sig.lens == lens)
    return (sig.valid > 0.5) & same


def next_signature(counts, lens, perm) -> PlanSignature:
    """Expected planner inputs after executing a plan with ``perm``:
    the slot at ``perm[i]`` next holds the sequence whose counts/lens
    sit in row ``i`` today. numpy/jnp agnostic."""
    xp = jnp if isinstance(counts, jnp.ndarray) else np
    n = counts.shape[0]
    ar = xp.arange(n, dtype=xp.int32)
    if xp is jnp:
        inv = jnp.zeros((n,), jnp.int32).at[perm].set(ar)
    else:
        inv = np.zeros(n, np.int32)
        inv[np.asarray(perm)] = ar
    one = jnp.float32(1.0) if xp is jnp else np.float32(1.0)
    return PlanSignature(counts[inv], lens[inv], one)


def invalid_signature(n_slots: int, M: int) -> PlanSignature:
    """Fixed-shape 'no carried plan' signature (scan carries need a
    uniform pytree even on sublayers that plan nothing)."""
    return PlanSignature(jnp.zeros((n_slots, M), jnp.float32),
                         jnp.zeros((n_slots,), jnp.float32),
                         jnp.float32(0.0))


def _rms(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    v = jnp.mean(xf * xf, -1, keepdims=True)
    return (xf * jax.lax.rsqrt(v + eps) * scale.astype(jnp.float32))


def expert_ffn(ew, h, act, compute_dtype, use_kernel: bool = False):
    """h: [E_local, R, d] normed inputs -> [E_local, R, d]."""
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.expert_ffn(h, ew["w_up"], ew["w_gate"], ew["w_down"], act)
    cdt = compute_dtype
    hc = h.astype(cdt)
    up = jnp.einsum("erd,edf->erf", hc, ew["w_up"].astype(cdt))
    gt = jnp.einsum("erd,edf->erf", hc, ew["w_gate"].astype(cdt))
    hh = act(gt) * up
    return jnp.einsum("erf,efd->erd", hh, ew["w_down"].astype(cdt))


# ---------------------------------------------------------------------------
# the plan
# ---------------------------------------------------------------------------

class ExchangePlan(NamedTuple):
    """Every decision about one exchange, as data.

    Static fields (python values, fixed at trace time) describe *how* to
    execute; traced fields describe *what* the router/condenser/planner
    decided for this step's tokens. ``estimate`` carries the analytic
    per-phase byte/latency model (None on single-device / unknown
    topologies) — dry-run ledgers and commsim report off it.
    """
    # -- static decisions ---------------------------------------------------
    mode: str                     # "vanilla" | "migrate"
    migrate: bool                 # mode == "migrate" and active (M > 1)
    condense: bool                # condensation active this call
    pipelined: bool               # chunked software pipeline vs sync
    capacity: int                 # per-(source, expert) dispatch capacity
    chunks: ChunkPlan             # capacity partition (1 chunk = sync)
    comm: CommContext             # collective strategy (never None)
    objective: str                # planner objective that produced this
    group_size: int               # condensation group G
    combine_slack: float          # migrate-mode combine buffer slack
    use_kernel: bool
    wire: str                     # "dense" | "dedup" (repro.condense.wire)
    wire_dtype: str               # "f32" | "bf16" | "f8e4m3" — precision
                                  # rows ship at across nodes (DESIGN §14)
    estimate: Optional[PlanEstimate]
    # -- routing (traced) ---------------------------------------------------
    expert_idx: Array             # [T, k] global expert ids
    gate_weights: Array           # [T, k] combine weights
    positions: Array              # [T, k] dispatch buffer positions
    valid: Array                  # [T, k] row takes a dispatch slot
    aux_loss: Array               # [] router load-balance loss
    dispatch_drop: Array          # [] fraction of kept rows dropped
    # -- condensation (repro.condense, DESIGN.md §10) -----------------------
    condense_plan: CondensePlan   # rep map, sim history, reuse signature
    # -- migration assignment ----------------------------------------------
    dest_global: Array            # [n_seq] new global slot per local slot
    traffic_before: Array         # [] weighted combine rows, identity plan
    traffic_after: Array          # [] weighted combine rows, this plan
    # -- traced wire ledger -------------------------------------------------
    inter_bytes_flat: Array
    inter_bytes_dedup: Array
    # -- plan lifecycle (DESIGN.md §9) --------------------------------------
    # signature: expected NEXT-exchange planner inputs (None when reuse
    # is off / nothing was planned); counters feed the MoEAux ledger.
    signature: Optional[PlanSignature] = None
    plans_built: Optional[Array] = None
    plans_reused: Optional[Array] = None
    reuse_mismatch: Optional[Array] = None
    # -- expert replication (objective "replicate", DESIGN.md §15) ----------
    # Frozen placement-cardinality decision: each device owns one extra
    # dispatch lane that can serve a replica of an intra-node peer's hot
    # expert. None (the default, and every other objective) = no lanes —
    # the executor's dense layout is unchanged.
    replica_src: Optional[Array] = None    # [M] int32 global expert id the
                                           # device's replica lane serves
                                           # (-1 = idle lane)
    replica_valid: Optional[Array] = None  # [T, k] bool — overflow copies
                                           # redirected to their expert's
                                           # replica lane

    # historical accessors — the condensation map now lives in the
    # nested CondensePlan (kept so call sites and tests read naturally)
    @property
    def rep_idx(self) -> Array:
        return self.condense_plan.rep_idx

    @property
    def s_next(self) -> Optional[Array]:
        return self.condense_plan.s_next

    @property
    def condense_rate(self) -> Array:
        return self.condense_plan.rate


class ExchangeAux(NamedTuple):
    """Executor outputs riding alongside ``y``."""
    sideband: Dict[str, Array]    # per-sequence state at its (new) home
    s_next: Optional[Array]       # similarity history (migrated if needed)
    moe: MoEAux
    cond_carry: Optional[Dict[str, Array]] = None
    # condense-reuse state for the next sublayer (DESIGN.md §10):
    # {"rep" [n_seq,S], "cexp" [n_seq,S], "age" [n_seq], "valid" [n_seq]}
    # — migrated to the sequences' new homes alongside the sideband
    wire_ef: Optional[Array] = None
    # lossy-wire error-feedback residual for the next step (§15):
    # [n_seq, S, d] f32, keyed by (slot, position), stop-gradded


# ---------------------------------------------------------------------------
# static schedule (shared by build_exchange_plan and the plan cache)
# ---------------------------------------------------------------------------

def plan_static_schedule(cfg: ModelConfig, luffy: LuffyConfig, topo, M: int,
                         T: int, d: int, capacity: int, bytes_per_el: int,
                         wire_dtype: str = "f32"
                         ) -> Tuple[bool, ChunkPlan, Optional[PlanEstimate]]:
    """All shape-keyed (token-independent) schedule decisions of one
    exchange: pipelined?, the :class:`ChunkPlan`, and the analytic
    :class:`PlanEstimate`. Host-side pure — ``repro.plan.cache`` builds
    ahead-of-time templates from exactly this function, so a cached
    template's schedule is identical to what ``build_exchange_plan``
    would decide for the same static key.

    ``luffy.pipeline_chunks <= 0`` requests the objective-planned chunk
    count (ROADMAP item): ``estimate_exchange(chunks=None)``'s existing
    1..16 search picks ``ChunkPlan.n_chunks`` instead of the CLI
    constant (an explicit positive CLI value still overrides).
    """
    m = cfg.moe
    pipelined = luffy.exec_mode == "pipeline" and M > 1
    # "decode_overlap" only reschedules the decode combine psum
    # (DESIGN.md §13); on the build/execute path it prices and chunks
    # exactly like sync.
    assert luffy.exec_mode in ("sync", "pipeline", "decode_overlap"), \
        luffy.exec_mode
    priced = topo is not None and M > 1
    ffn_ms = 0.0
    if priced:
        ffn_rows = m.num_experts * capacity   # static rows (M*C*E_local)
        # 4·d·d_ff flops/row (up+down matmuls) — the repo-wide pricing
        # convention (commsim._expert_flops, dryrun ledger, objective
        # sweep); gate matmuls are deliberately excluded everywhere so
        # objective decisions stay consistent with the calibrated model
        ffn_ms = ffn_rows * 4.0 * d * m.d_ff / luffy.gpu_speed * 1e3
    # per-chunk overhead: the measured fit when calibration set one
    # (repro.obs.calibrate via LuffyConfig), the constant otherwise
    o_ms = resolve_chunk_overhead_ms(luffy.chunk_overhead_ms)
    req = luffy.pipeline_chunks if pipelined else 1
    if pipelined and req <= 0:
        if priced:
            req = estimate_exchange(T, m.top_k, d, topo=topo,
                                    bytes_per_el=bytes_per_el,
                                    ffn_ms=ffn_ms, chunks=None,
                                    chunk_overhead_ms=o_ms,
                                    wire_dtype=wire_dtype).chunks
        else:
            req = DEFAULT_PIPELINE_CHUNKS   # nothing to price against
    chunks = plan_chunks(capacity, req)
    est = None
    if priced:
        est = estimate_exchange(T, m.top_k, d, topo=topo,
                                bytes_per_el=bytes_per_el, ffn_ms=ffn_ms,
                                chunks=chunks.n_chunks,
                                chunk_overhead_ms=o_ms,
                                wire_dtype=wire_dtype)
    return pipelined, chunks, est


# ---------------------------------------------------------------------------
# build
# ---------------------------------------------------------------------------

def build_exchange_plan(gate: GateOutput, xn: Array, cfg: ModelConfig,
                        luffy: LuffyConfig, comm: CommContext, *,
                        mode: str, capacity: int,
                        sideband: Dict[str, Array],
                        threshold=None, s_prev: Optional[Array] = None,
                        group_size: int = 128, combine_slack: float = 1.0,
                        use_kernel: bool = False,
                        reuse_from: Optional[Union["ExchangePlan",
                                                   PlanSignature]] = None,
                        condense_reuse_from: Optional[CondenseCarry] = None
                        ) -> ExchangePlan:
    """Decide one exchange: condensation map, dispatch slots/drops, the
    migration assignment (via the ``luffy.plan_objective`` registry
    entry), the chunk schedule, and the analytic phase estimates.

    gate: router output over ``xn`` [T, d] (normed tokens, T = n_seq*S);
    sideband must hold ``seq_len`` [n_seq]. Pure function of the routing
    — no payload bytes move here.

    reuse_from (DESIGN.md §9): a prior :class:`ExchangePlan` (or its
    :class:`PlanSignature`) from an earlier sublayer of the same
    forward. Under ``luffy.plan_reuse="signature"`` the carried decision
    is revalidated with the routing-signature compare and, on a match,
    the migration greedy is skipped — the sequences already sit where a
    replan would put them, so the emitted plan (``home_plan``) is
    bit-identical to what the full planner would return. On a mismatch
    the stale plan is discarded and a full replan runs (counted in
    ``reuse_mismatch``). ``"always"`` skips revalidation entirely
    (trusted reuse; forward outputs may then differ from ``"off"``).
    """
    global BUILD_CALLS
    BUILD_CALLS += 1
    m = cfg.moe
    T, d = xn.shape
    n_seq = sideband["seq_len"].shape[0]
    S = T // n_seq
    E = m.num_experts
    M = comm.size()
    assert E % M == 0, (E, M)
    E_local = E // M
    my = comm.index()
    C = capacity
    expert_idx, gate_w = gate.expert_idx, gate.gate_weights   # [T,k]

    # token validity (length padding)
    pos_in_seq = jnp.tile(jnp.arange(S, dtype=jnp.int32)[None], (n_seq, 1))
    token_valid = (pos_in_seq < sideband["seq_len"][:, None]).reshape(T)
    keep = jnp.tile(token_valid[:, None], (1, m.top_k))

    # ---- token condensation (§V, repro.condense) -------------------------
    do_condense = luffy.enable_condensation and mode != "decode"
    if do_condense:
        with obs_trace.phase("condense") as _sp:
            cp = cplan.build_condense_plan(
                xn, expert_idx[:, 0], threshold, group_size=group_size,
                s_prev=(None if s_prev is None
                        else s_prev.reshape(-1, group_size, group_size)),
                s1=luffy.s1, s2=luffy.s2, use_kernel=use_kernel,
                backend=luffy.similarity_backend, lsh_bits=luffy.lsh_bits,
                lsh_seed=luffy.lsh_seed, carry=condense_reuse_from,
                reuse_mode=luffy.condense_reuse,
                max_age=luffy.condense_reuse_max_age)
            cp = _sp.fence(cp)
        keep = keep & cp.is_rep[:, None]
    else:
        cp = identity_condense_plan(T, backend=luffy.similarity_backend)

    # ---- dispatch positions & drops --------------------------------------
    pos = dispatch_positions(expert_idx, keep, E)             # [T,k]
    valid = keep & (pos < C)
    kept = jnp.sum(keep.astype(jnp.float32))
    d_drop = 1.0 - jnp.sum(valid.astype(jnp.float32)) / jnp.maximum(kept, 1.0)

    # ---- execution schedule + phase estimates ----------------------------
    from repro.models.blocks import _dtype
    cdt = _dtype(cfg.compute_dtype)
    topo = comm.topology
    wire_dtype = wdt.validate_wire_dtype(luffy.wire_dtype)
    pipelined, chunks, est = plan_static_schedule(
        cfg, luffy, topo, M, T, d, C,
        bytes_per_el=jnp.dtype(cdt).itemsize, wire_dtype=wire_dtype)

    # ---- wire format (DESIGN.md §10, §15) --------------------------------
    # universal: the dedup wire now applies in EVERY mode — migrate-mode
    # combine re-addresses through the dest-keyed map and pipelined
    # execution chunks the unique-row capacity (§15), so only the comm
    # strategy gates it
    wire = ("dedup" if (luffy.hier_dedup == "on" and comm.mode == "hier"
                        and M > 1) else "dense")

    # ---- hot-expert replication (objective "replicate", DESIGN.md §15) ---
    # HierMoE-style placement cardinality: replicate each node's hottest
    # expert onto an intra-node peer's spare dispatch lane when the
    # modeled serialization relief beats the replica-consistency psum.
    # The dedup wire takes precedence (its unique-row packing already
    # removes the duplicate bytes the replica would shortcut); the
    # migration half of the objective still runs below.
    replica_src = replica_valid = None
    lane = (luffy.plan_objective == "replicate" and mode == "migrate"
            and luffy.enable_migration and M > 1 and wire == "dense"
            and topo is not None and topo.hierarchical
            and topo.devices_per_node > 1)
    if lane:
        ohe = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32) \
            * keep[..., None].astype(jnp.float32)
        # demand per expert (pre-drop: replication exists to relieve the
        # overflow the capacity bound is about to drop), psum-replicated
        # so every device freezes the SAME placement
        load_e = jax.lax.psum(ohe.sum((0, 1)), comm.axis_name)    # [E]
        replica_src = objectives.plan_expert_replicas(
            load_e, e_local=E_local, topo=topo,
            ffn_ms=(0.0 if est is None else est.ffn_ms),
            d_model=d, d_ff=m.d_ff,
            bytes_per_el=jnp.dtype(cdt).itemsize)
        host_of = jnp.full((E,), -1, jnp.int32).at[
            jnp.where(replica_src >= 0, replica_src, 0)].max(
            jnp.where(replica_src >= 0,
                      jnp.arange(M, dtype=jnp.int32), -1), mode="drop")
        # redirect rule: first-overflow copies (C <= pos < 2C) of a
        # replicated expert take slot pos - C on the host's replica lane
        # — strictly fewer drops; rows with pos < C are untouched, so
        # the lane-less layout is bit-identical where it was valid
        replica_valid = keep & (pos >= C) & (pos < 2 * C) \
            & (host_of[expert_idx] >= 0)
        d_drop = 1.0 - (jnp.sum(valid.astype(jnp.float32))
                        + jnp.sum(replica_valid.astype(jnp.float32))) \
            / jnp.maximum(kept, 1.0)

    # ---- inter-node traffic ledger (DESIGN.md §5) ------------------------
    # redirected replica rows count too: the host sits on the owner's
    # node, so expert_idx still keys the destination node correctly
    v_ledger = valid if replica_valid is None else (valid | replica_valid)
    if topo is not None and topo.hierarchical and M > 1:
        row_bytes = float((d + 2) * jnp.dtype(cdt).itemsize)
        ib_flat, ib_dedup = comm_ledger.dispatch_node_ledger(
            expert_idx, v_ledger, my, e_local=E_local, topo=topo,
            row_bytes=row_bytes)
        if comm.mode != "hier":
            ib_dedup = ib_flat      # the flat path ships every copy
    else:
        ib_flat = ib_dedup = jnp.float32(0.0)

    # ---- migration plan (§IV) — BEFORE dispatch so combine can be
    # re-addressed. Replicated within the model row. -----------------------
    migrate = (mode == "migrate") and luffy.enable_migration and M > 1
    reuse_mode = luffy.plan_reuse
    reuse_enabled = reuse_mode != "off"
    z = jnp.float32(0.0)
    built = reused = mismatch = z
    sig_out: Optional[PlanSignature] = None
    if migrate:
        n_slots = M * n_seq
        dev_of_e = expert_idx // E_local                      # [T,k]
        oh = jax.nn.one_hot(dev_of_e, M, dtype=jnp.float32) \
            * valid[..., None].astype(jnp.float32)
        counts_local = oh.reshape(n_seq, S, m.top_k, M).sum((1, 2))  # [n_seq,M]
        counts_g = jax.lax.all_gather(counts_local, comm.axis_name, axis=0,
                                      tiled=True)             # [M*n_seq, M]
        lens_g = jax.lax.all_gather(sideband["seq_len"], comm.axis_name,
                                    axis=0, tiled=True)       # [M*n_seq]
        lens_f = lens_g.astype(jnp.float32)
        o_ms = resolve_chunk_overhead_ms(luffy.chunk_overhead_ms)
        octx = objectives.ObjectiveContext(topo=topo,
                                           chunk_overhead_ms=o_ms)
        if est is not None:
            octx = objectives.ObjectiveContext(
                topo=topo, ffn_ms=est.ffn_ms,
                dispatch_intra_ms=est.intra_dispatch_bytes
                / topo.intra_bw * 1e3,
                dispatch_inter_ms=est.inter_dispatch_bytes
                / topo.inter_bw * 1e3,
                chunks=chunks.n_chunks,
                row_bytes=float(d * jnp.dtype(cdt).itemsize),
                chunk_overhead_ms=o_ms)

        def _replan(cg, lf):
            return tuple(objectives.plan_migration_with_objective(
                cg, lf, n_seq, objective=luffy.plan_objective, ctx=octx,
                q=luffy.q, d_model=d, speed=luffy.gpu_speed))

        sig_in = None
        if reuse_from is not None:
            sig_in = (reuse_from.signature
                      if isinstance(reuse_from, ExchangePlan)
                      else reuse_from)
        # Reuse is sound only under the "traffic" objective: its greedy
        # re-derives the executed placement from a matching signature.
        # The "overlap" portfolio may execute the exposure candidate,
        # which the next frame's greedy would NOT re-derive — so other
        # objectives emit carries that never validate (below), and the
        # cond machinery is still built for them, keeping the compiled
        # graph identical across objectives and plan_reuse modes.
        reuse_capable = luffy.plan_objective == "traffic"
        if sig_in is not None:
            # The cond machinery is built whenever a carry is threaded —
            # for plan_reuse="off" too, with the carried ``valid`` pinned
            # to 0.0 so revalidation never fires at runtime. Rationale:
            # the greedy has float near-ties, so two *structurally
            # different* compiled graphs may pick different (equally
            # valid) plans; keeping "off" and "signature" graphs
            # identical makes their forwards bit-comparable, which is
            # the reuse correctness guarantee the tests assert.
            have = sig_in.valid > 0.5
            if reuse_mode == "always":
                match = have
            else:                                   # "off" | "signature"
                same = routing_signature_matches(sig_in, counts_g, lens_f)
                match = have & same
                mismatch = (have & ~same).astype(jnp.float32)
            lc_np = objectives.traffic_link_cost(topo)
            lc = None if lc_np is None else jnp.asarray(lc_np, jnp.float32)

            def _reuse(cg, lf):
                # signature matched: the (deterministic) greedy would
                # re-derive the current placement, so skip it and emit
                # the home plan with the exact same traffic ledger
                return tuple(mig.home_plan(cg, n_seq, link_cost=lc))

            mplan = mig.MigrationPlan(*jax.lax.cond(
                match, _reuse, _replan, counts_g, lens_f))
            mf = match.astype(jnp.float32)
            built, reused = 1.0 - mf, mf
        else:
            mplan = mig.MigrationPlan(*_replan(counts_g, lens_f))
            built = jnp.float32(1.0)
        my_slots = my * n_seq + jnp.arange(n_seq, dtype=jnp.int32)
        dest_global = mplan.perm[my_slots]                    # [n_seq]
        t_before, t_after = mplan.traffic_before, mplan.traffic_after
        if reuse_enabled or sig_in is not None:
            sig_out = next_signature(counts_g, lens_f, mplan.perm)
            if not (reuse_enabled and reuse_capable):
                # "off", or an objective that cannot soundly reuse:
                # the carry never revalidates (always replans)
                sig_out = sig_out._replace(valid=jnp.float32(0.0))
    else:
        dest_global = my * n_seq + jnp.arange(n_seq, dtype=jnp.int32)
        t_before = t_after = jnp.float32(0.0)
    if sig_out is None and (reuse_enabled or reuse_from is not None):
        # fixed-shape carry even when nothing was planned (vanilla mode,
        # single device): an invalid signature that never revalidates
        sig_out = invalid_signature(M * n_seq, M)

    return ExchangePlan(
        mode=mode, migrate=migrate, condense=do_condense,
        pipelined=pipelined, capacity=C, chunks=chunks, comm=comm,
        objective=luffy.plan_objective, group_size=group_size,
        combine_slack=combine_slack, use_kernel=use_kernel, wire=wire,
        wire_dtype=wire_dtype, estimate=est,
        expert_idx=expert_idx, gate_weights=gate_w, positions=pos,
        valid=valid, aux_loss=gate.aux_loss, dispatch_drop=d_drop,
        condense_plan=cp,
        dest_global=dest_global, traffic_before=t_before,
        traffic_after=t_after, inter_bytes_flat=ib_flat,
        inter_bytes_dedup=ib_dedup, signature=sig_out,
        plans_built=built, plans_reused=reused, reuse_mismatch=mismatch,
        replica_src=replica_src, replica_valid=replica_valid)


# ---------------------------------------------------------------------------
# execute
# ---------------------------------------------------------------------------

def execute_plan(params, x: Array, sideband: Dict[str, Array],
                 plan: ExchangePlan, cfg: ModelConfig, *,
                 wire_ef: Optional[Array] = None
                 ) -> Tuple[Array, ExchangeAux]:
    """Move the bytes the plan prescribes: pack dispatch buffers, run the
    (optionally pipelined) dispatch → expert FFN → combine exchange,
    regroup/un-condense, apply shared experts. No decisions are made
    here — the plan is the single source of truth, so the train forward
    and the serving prefill execute identically.

    x: [n_seq, S, d] pre-norm hidden. Returns ``(y, ExchangeAux)``; in
    vanilla mode ``y = x + moe_delta``, in migrate mode ``y`` is the full
    post-block hidden materialized at *new* slots.

    ``wire_ef`` (DESIGN.md §15): the carried positional error-feedback
    residual for a lossy wire, [n_seq, S, d] f32. It is added to the
    *shipped payload only* — the residual connection, the router and
    the aux ledger all keep the exact hidden — and the new residual
    ``payload - dequant(quant(payload))`` is returned on
    ``ExchangeAux.wire_ef`` for the caller to carry into the NEXT
    step's payload at the same (slot, position). Quantization is
    per-row, so the token-major residual computed here equals the
    residual of every shipped copy of that row.
    """
    from repro.models.blocks import _act, _dtype
    m = cfg.moe
    cdt = _dtype(cfg.compute_dtype)
    act = _act(cfg.act)
    n_seq, S, d = x.shape
    T = n_seq * S
    E = m.num_experts
    comm = plan.comm
    M = comm.size()
    E_local = E // M
    my = comm.index()
    C = plan.capacity
    migrate = plan.migrate
    use_kernel = plan.use_kernel
    group_size = plan.group_size
    expert_idx, gate_w = plan.expert_idx, plan.gate_weights
    pos, valid = plan.positions, plan.valid
    rep_idx, s_next = plan.rep_idx, plan.s_next
    dest_global = plan.dest_global

    xf = x.reshape(T, d)

    # ---- lossy-wire error feedback (DESIGN.md §15) -----------------------
    # x_pay is what the dispatch buffers carry; xf stays exact for the
    # residual connection. The new residual is stop-gradded state, not a
    # differentiable path.
    x_pay = xf
    ef_next = None
    if wire_ef is not None:
        x_pay = xf + wire_ef.reshape(T, d).astype(xf.dtype)
        if plan.wire_dtype != "f32" and M > 1:
            pc = x_pay.astype(cdt)
            q_ef, sc_ef = wdt.quantize_rows(pc, plan.wire_dtype)
            deq_ef = wdt.dequantize_rows(q_ef, sc_ef, cdt, d)
            ef_next = jax.lax.stop_gradient(
                (pc - deq_ef).astype(jnp.float32).reshape(n_seq, S, d))
        else:       # exact wire (or nothing crosses it): residual dies
            ef_next = jnp.zeros((n_seq, S, d), jnp.float32)

    def _finish(y_tok, new_sideband, s_next, c_drop, local_frac, shipped):
        """Shared executor tail: un-condense (token_to_token, §VI), the
        condense-reuse carry (migrated with sequences), shared experts
        and the aux ledger."""
        cpn = plan.condense_plan
        carry_sig = cpn.signature if plan.condense else None
        cexp_sb = age_sb = valid_sb = None
        rep_carry = None
        if carry_sig is not None:
            cexp_sb = carry_sig.expert.reshape(n_seq, S).astype(jnp.int32)
            age_sb, valid_sb = carry_sig.age, carry_sig.valid
        if plan.condense:
            if not migrate:
                y_tok = uncondense(y_tok, rep_idx)
                rep_carry = (rep_idx % group_size).reshape(n_seq, S)
            else:
                # rep map (and the condense-reuse signature) migrated as
                # sideband: everything per-sequence rides with its owner
                ex = {"rep": (rep_idx % S).reshape(n_seq, S)
                      .astype(jnp.int32)}
                if carry_sig is not None:
                    ex.update(cexp=cexp_sb, cage=age_sb, cvalid=valid_sb)
                mig_sb = _exchange_sideband(ex, dest_global, n_seq, M, comm)
                rep_sb = mig_sb["rep"]
                if carry_sig is not None:
                    cexp_sb, age_sb, valid_sb = (
                        mig_sb["cexp"], mig_sb["cage"], mig_sb["cvalid"])
                yg = y_tok.reshape(n_seq, S, d)
                y_tok = jnp.take_along_axis(yg, rep_sb[..., None], axis=1
                                            ).reshape(T, d)
                # within-group position survives the within-seq one
                rep_carry = rep_sb % group_size
            if s_next is not None and migrate:
                ng = S // group_size
                s_mig = s_next.reshape(n_seq, ng, group_size, group_size)
                s_next = _exchange_sideband(
                    {"s": s_mig.astype(jnp.bfloat16)}, dest_global, n_seq,
                    M, comm)["s"].astype(jnp.float32)
                s_next = s_next.reshape(-1, group_size, group_size)

        y_out = y_tok.reshape(n_seq, S, d)

        # ---- shared experts (always-on, llama4-style) ---------------------
        if "shared" in params:
            from repro.models.blocks import ffn_apply
            sh = ffn_apply({"w_up": params["shared"]["w_up"],
                            "w_gate": params["shared"]["w_gate"],
                            "w_down": params["shared"]["w_down"]},
                           cfg, _rms(y_out if migrate
                                     else x.reshape(n_seq, S, d),
                                     params["norm"]["scale"]).astype(cdt))
            y_out = y_out + sh.astype(y_out.dtype)

        zc = jnp.float32(0.0)
        aux = MoEAux(
            plan.aux_loss, plan.dispatch_drop, c_drop, plan.condense_rate,
            local_frac, plan.traffic_before, plan.traffic_after,
            plan.inter_bytes_flat, plan.inter_bytes_dedup,
            zc if plan.plans_built is None else plan.plans_built,
            zc if plan.plans_reused is None else plan.plans_reused,
            zc if plan.reuse_mismatch is None else plan.reuse_mismatch,
            cpn.measured_pairs,
            zc if cpn.built is None else cpn.built,
            zc if cpn.reused is None else cpn.reused,
            shipped)
        cond_carry = None
        if carry_sig is not None:
            cond_carry = {"rep": rep_carry.astype(jnp.int32),
                          "cexp": cexp_sb, "age": age_sb,
                          "valid": valid_sb}
        return y_out, ExchangeAux(sideband=new_sideband, s_next=s_next,
                                  moe=aux, cond_carry=cond_carry,
                                  wire_ef=ef_next)

    # ---- deduplicated hier wire (DESIGN.md §10, §14, §15) ----------------
    # universal: vanilla, migrate (dest-keyed combine) and pipelined
    # (unique-row chunking) all run the dedup wire now
    if plan.wire == "dedup":
        assert plan.replica_src is None, plan.objective
        dchunks = None
        if plan.pipelined:
            L_loc = jax.lax.axis_size(comm.local_axis)
            dchunks = plan_unique_chunks(
                cwire.dedup_capacity(T, E_local, L_loc, C),
                plan.chunks.n_chunks)
        dest_gpos = prim_tk = None
        if migrate:
            # each copy's destination global position in the migrated
            # frame: dest device × T + position within it — the plane
            # dedup_combine_migrate re-addresses the combine through
            tok_ids = jnp.arange(T, dtype=jnp.int32)
            dslot_g = dest_global[tok_ids // S]
            dest_gpos = ((dslot_g // n_seq) * T
                         + (dslot_g % n_seq) * S + (tok_ids % S))
            prim_tk = jnp.broadcast_to(
                (jnp.arange(m.top_k) == 0)[None, :], (T, m.top_k))
        with obs_trace.phase("dispatch") as _sp:
            x_rows, gw_rows, rvalid, wstate = cwire.dedup_dispatch(
                x_pay.astype(cdt), expert_idx, gate_w, valid, pos,
                comm=comm, e_local=E_local, capacity=C,
                wire_dtype=plan.wire_dtype, use_kernel=use_kernel,
                dest_gpos=dest_gpos, prim=prim_tk, chunks=dchunks)
            x_rows = _sp.fence(x_rows)
        with obs_trace.phase("expert_ffn") as _sp:
            h = _rms(x_rows, params["norm"]["scale"]).astype(cdt)
            y_rows = expert_ffn(params["experts"],
                                h.reshape(E_local, M * C, d), act,
                                cdt, use_kernel=use_kernel
                                ).reshape(E_local, M, C, d)
            y_rows = _sp.fence(y_rows)
        with obs_trace.phase("combine") as _sp:
            if not migrate:
                delta = cwire.dedup_combine(y_rows * gw_rows[..., None],
                                            wstate, comm=comm,
                                            wire_dtype=plan.wire_dtype,
                                            chunks=dchunks)
                y_tok = xf + delta.astype(xf.dtype)
                c_drop = jnp.float32(0.0)
                local_frac = jnp.float32(1.0 / M)
                new_sideband = dict(sideband)
            else:
                # gate-weighted + the primary copy's residual: the
                # dest-keyed combine materializes the post-block hidden
                # at NEW slots (no drop path — the migration perm is a
                # bijection, every destination receives exactly T rows)
                out_rows = (y_rows * gw_rows[..., None]
                            + x_rows * wstate["prim"][..., None])
                mchunks = (plan_unique_chunks(T, plan.chunks.n_chunks)
                           if plan.pipelined else None)
                y_mig = cwire.dedup_combine_migrate(
                    out_rows, wstate, comm=comm,
                    wire_dtype=plan.wire_dtype, chunks=mchunks)
                y_tok = y_mig.astype(xf.dtype)
                c_drop = jnp.float32(0.0)
                dd_rows = jnp.where(wstate["dgpos"] >= 0,
                                    wstate["dgpos"] // T, -1)
                local_frac = (jnp.sum((dd_rows == my).astype(jnp.float32))
                              / jnp.maximum(
                                  jnp.sum(rvalid.astype(jnp.float32)),
                                  1.0))
                new_sideband = _exchange_sideband(
                    sideband, dest_global, n_seq, M, comm)
            y_tok = _sp.fence(y_tok)
        # executed wire accounting: unique rows × the wire row bytes —
        # the same wire_row_bytes the estimate divides by, so
        # shipped == inter_bytes_dedup / precision == flat / (dedup ×
        # precision) exactly (the §14 ledger contract; dispatch is
        # mode-independent, so the law holds in all three modes)
        row_bytes = wdt.wire_row_bytes(d, plan.wire_dtype,
                                       jnp.dtype(cdt).itemsize)
        return _finish(y_tok, new_sideband, s_next,
                       c_drop, local_frac,
                       wstate["shipped_rows"] * jnp.float32(row_bytes))

    # ---- build dispatch buffers ------------------------------------------
    # payload row: [x_raw(d), gate_w, is_primary]; meta: (dest_slot+1, pos)
    # Replica lanes (objective "replicate", §15): each device's buffer
    # grows one lane (row index [M, n_lanes] flattened); first-overflow
    # copies of a replicated expert redirect to the HOST device's lane
    # at slot pos - C. n_lanes == E_local (no lanes) leaves row == e_f,
    # the historical layout, bit-for-bit.
    has_lane = plan.replica_src is not None
    n_lanes = E_local + (1 if has_lane else 0)
    R_rows = M * n_lanes
    is_primary = (jnp.arange(m.top_k) == 0)[None, :]          # [1,k]
    tok_slot = jnp.tile((jnp.arange(T, dtype=jnp.int32) // S)[:, None],
                        (1, m.top_k))                         # local seq slot
    tok_pos = jnp.tile((jnp.arange(T, dtype=jnp.int32) % S)[:, None],
                       (1, m.top_k))
    dest_of_tok = dest_global[tok_slot]                       # [T,k]

    e_f = expert_idx.reshape(-1)
    p_f = pos.reshape(-1)
    v_f = valid.reshape(-1)
    row_f = (e_f // E_local) * n_lanes + (e_f % E_local)
    if has_lane:
        rep_src = plan.replica_src
        host_of = jnp.full((E,), -1, jnp.int32).at[
            jnp.where(rep_src >= 0, rep_src, 0)].max(
            jnp.where(rep_src >= 0, jnp.arange(M, dtype=jnp.int32), -1),
            mode="drop")
        rv_f = plan.replica_valid.reshape(-1)
        host_row = host_of[jnp.where(rv_f, e_f, 0)] * n_lanes + E_local
        row_f = jnp.where(rv_f, host_row, row_f)
        p_f = jnp.where(rv_f, p_f - C, p_f)
        v_f = v_f | rv_f
    payload = jnp.concatenate([
        jnp.tile(x_pay.astype(cdt)[:, None], (1, m.top_k, 1)),
        gate_w[..., None].astype(cdt),
        jnp.broadcast_to(is_primary, (T, m.top_k))[..., None].astype(cdt),
    ], axis=-1).reshape(-1, d + 2)                            # [T*k, d+2]
    meta = jnp.stack([dest_of_tok + 1, tok_pos], -1).reshape(-1, 2)

    with obs_trace.phase("dispatch_pack") as _sp:
        buf = jnp.zeros((R_rows, C, d + 2), cdt)
        mbuf = jnp.zeros((R_rows, C, 2), jnp.int32)
        p_safe = jnp.where(v_f, p_f, 0)
        r_safe = jnp.where(v_f, row_f, 0)
        buf = buf.at[r_safe, p_safe].add(
            payload * v_f[:, None].astype(cdt), mode="drop")
        mbuf = mbuf.at[r_safe, p_safe].add(
            meta * v_f[:, None].astype(jnp.int32), mode="drop")
        buf = _sp.fence(buf)

    # replica-lane expert weights: the lane serves replica_src[my],
    # fetched from its intra-node owner over the cheap links (the
    # forward fan-in replica_consistency_ms prices); an idle lane gets
    # zero weights, so its (empty) rows produce exact zeros
    ew = params["experts"]
    if has_lane:
        L_loc = jax.lax.axis_size(comm.local_axis)
        src = plan.replica_src[my]
        src_safe = jnp.maximum(src, 0)
        owner_row = (src_safe // E_local) % L_loc * E_local \
            + src_safe % E_local
        live = (src >= 0).astype(cdt)

        def _lane_w(wk):
            return comm.local_all_gather(wk)[owner_row] * live

        ew = {k: jnp.concatenate([ew[k], _lane_w(ew[k])[None]], axis=0)
              for k in ("w_up", "w_gate", "w_down")}

    # ---- dispatch → expert FFN → (vanilla) combine ------------------------
    # plan.pipelined chunks the static capacity dim and runs the
    # repro.sched software pipeline: chunk k's collective is issued before
    # chunk k-1's FFN result is consumed (DESIGN.md §6). Bit-identical to
    # sync: capacity slicing commutes with the data-movement-only
    # collectives and the row-wise FFN, and chunk results are reassembled
    # in the sync layout before any order-sensitive step (the migrate-mode
    # regroup sorts across ALL rows, so it stays a post-pipeline barrier).
    def _ffn_rows(rows_k):
        """rows_k: [n_lanes, M, Ck, d+2] -> (out, prim) same leading dims
        (lane n_lanes-1, when present, runs the replica's weights)."""
        xr = rows_k[..., :d]
        gw = rows_k[..., d:d + 1]
        prim_k = rows_k[..., d + 1:d + 2]
        ck = rows_k.shape[2]
        h = _rms(xr, params["norm"]["scale"]).astype(cdt)
        y = expert_ffn(ew, h.reshape(n_lanes, M * ck, d),
                       act, cdt, use_kernel=use_kernel) \
            .reshape(n_lanes, M, ck, d)
        out_k = y * gw
        if migrate:
            out_k = out_k + xr * prim_k    # primary copy carries residual
        return out_k, prim_k

    if plan.pipelined:
        cplan = plan.chunks

        def _disp(k):
            # vanilla needs no row metadata — exchanging it would put a
            # dead collective on the pipelined critical path (the barrier
            # keeps payloads live, so XLA could not DCE it there)
            o, s = cplan.offsets[k], cplan.sizes[k]
            bk = cwire.ship_rows(comm.all_to_all,
                                 jax.lax.slice_in_dim(buf, o, o + s, axis=1),
                                 d, plan.wire_dtype)
            if not migrate:
                return bk
            return bk, comm.all_to_all(jax.lax.slice_in_dim(mbuf, o, o + s,
                                                            axis=1))

        def _compute(k, payload):
            bk, mk = payload if migrate else (payload, None)
            s = cplan.sizes[k]
            rows_k = bk.reshape(M, n_lanes, s, d + 2).transpose(1, 0, 2, 3)
            if not migrate:
                return _ffn_rows(rows_k)
            meta_k = mk.reshape(M, n_lanes, s, 2).transpose(1, 0, 2, 3)
            return _ffn_rows(rows_k) + (meta_k,)

        with obs_trace.phase("pipeline_exchange") as _psp:
            if not migrate:
                def _comb(k, res):
                    out_k = res[0]             # [n_lanes, M, Ck, d]
                    back_k = out_k.transpose(1, 0, 2, 3) \
                                  .reshape(R_rows, out_k.shape[2], d)
                    return cwire.ship_rows(comm.combine, back_k, d,
                                           plan.wire_dtype)

                _, backs = run_pipeline(cplan.n_chunks, dispatch=_disp,
                                        compute=_compute, combine=_comb)
                back = jnp.concatenate(backs, axis=1)        # [R_rows, C, d]
                back = _psp.fence(back)
            else:
                outs, _ = run_pipeline(cplan.n_chunks, dispatch=_disp,
                                       compute=_compute)
                out_rows = jnp.concatenate([o for o, _, _ in outs],
                                           axis=2) \
                              .reshape(n_lanes, M * C, d)
                prim = jnp.concatenate([p for _, p, _ in outs], axis=2) \
                          .reshape(n_lanes, M * C, 1)
                rmeta = jnp.concatenate([m for _, _, m in outs], axis=2) \
                           .reshape(n_lanes, M * C, 2)
                out_rows = _psp.fence(out_rows)
    else:
        with obs_trace.phase("dispatch") as _sp:
            if M > 1:
                # activation columns ship at the wire dtype; the int32
                # meta buffer (slot map) never quantizes (DESIGN.md §14)
                buf = cwire.ship_rows(comm.all_to_all, buf, d,
                                      plan.wire_dtype)
                mbuf = comm.all_to_all(mbuf)
            # [M_src * n_lanes, C, .] -> [n_lanes, M_src, C, .]
            rows4 = buf.reshape(M, n_lanes, C, d + 2).transpose(1, 0, 2, 3)
            rmeta = mbuf.reshape(M, n_lanes, C, 2).transpose(1, 0, 2, 3) \
                        .reshape(n_lanes, M * C, 2)
            rows4 = _sp.fence(rows4)
        with obs_trace.phase("expert_ffn") as _sp:
            out4, prim4 = _ffn_rows(rows4)
            out4 = _sp.fence(out4)
        out_rows = out4.reshape(n_lanes, M * C, d)
        prim = prim4.reshape(n_lanes, M * C, 1)
        if not migrate:
            with obs_trace.phase("combine") as _sp:
                back = out_rows.reshape(n_lanes, M, C, d) \
                               .transpose(1, 0, 2, 3).reshape(R_rows, C, d)
                if M > 1:
                    back = cwire.ship_rows(comm.combine, back, d,
                                           plan.wire_dtype)
                back = _sp.fence(back)

    # ---- combine ----------------------------------------------------------
    if not migrate:
        # vanilla: rows returned to their source in dispatch layout —
        # replica copies merge in the same fixed per-copy k-order sum
        # as owner copies (the deterministic replica-merge order)
        with obs_trace.phase("combine_unpack"):
            vals = back[r_safe, p_safe] * v_f[:, None].astype(cdt)
            delta = jnp.sum(vals.reshape(T, m.top_k, d), axis=1)
            y_tok = xf + delta.astype(xf.dtype)
        c_drop = jnp.float32(0.0)
        local_frac = jnp.float32(1.0 / M)
        new_sideband = dict(sideband)
    else:
        with obs_trace.phase("combine"):
            # regroup rows by destination device (residual rows first)
            R = n_lanes * M * C
            o_f = out_rows.reshape(R, d)
            dslot = rmeta[..., 0].reshape(R) - 1               # -1 = empty row
            rpos = rmeta[..., 1].reshape(R)
            rprim = prim.reshape(R) > 0.5
            rvalid = dslot >= 0
            ddev = jnp.where(rvalid, dslot // n_seq, M)        # M = dummy bin
            prio = (~rvalid).astype(jnp.int32) * 2 + (~rprim).astype(jnp.int32)
            order = jnp.argsort(prio, stable=True)
            o_f, dslot, rpos, ddev, rvalid = (a[order] for a in
                                              (o_f, dslot, rpos, ddev, rvalid))
            C_comb = max(8, int(math.ceil(
                plan.combine_slack * n_lanes * C / 8)) * 8)
            oh = jax.nn.one_hot(ddev, M, dtype=jnp.int32)
            rank = (jnp.cumsum(oh, axis=0) - oh)[jnp.arange(R), jnp.where(
                rvalid, ddev, 0)]
            keep_c = rvalid & (rank < C_comb)
            n_rv = jnp.sum(rvalid.astype(jnp.float32))
            c_drop = 1.0 - jnp.sum(keep_c.astype(jnp.float32)) / jnp.maximum(
                n_rv, 1.0)
            local_frac = jnp.sum((keep_c & (ddev == my)).astype(jnp.float32)) \
                / jnp.maximum(n_rv, 1.0)
            dd_s = jnp.where(keep_c, ddev, 0)
            rk_s = jnp.where(keep_c, rank, 0)
            cbuf = jnp.zeros((M, C_comb, d), cdt).at[dd_s, rk_s].add(
                o_f * keep_c[:, None].astype(cdt), mode="drop")
            cmeta = jnp.zeros((M, C_comb, 2), jnp.int32).at[dd_s, rk_s].add(
                jnp.stack([jnp.where(keep_c, dslot % n_seq + 1, 0),
                           jnp.where(keep_c, rpos, 0)], -1), mode="drop")
            if M > 1:
                cbuf = cwire.ship_rows(comm.combine, cbuf, d, plan.wire_dtype)
                cmeta = comm.combine(cmeta)
            rs = cbuf.reshape(M * C_comb, d)
            rslot = cmeta[..., 0].reshape(-1) - 1
            rp = cmeta[..., 1].reshape(-1)
            ok = rslot >= 0
            y_grid = jnp.zeros((n_seq, S, d), cdt).at[
                jnp.where(ok, rslot, 0), jnp.where(ok, rp, 0)].add(
                rs * ok[:, None].astype(cdt), mode="drop")
            y_tok = y_grid.reshape(T, d).astype(xf.dtype)
            # sideband travels with sequences
            new_sideband = _exchange_sideband(
                sideband, dest_global, n_seq, M, comm)

    return _finish(y_tok, new_sideband, s_next, c_drop, local_frac,
                   jnp.float32(0.0))


def instantiate_plan(template: ExchangePlan, gate: GateOutput, xn: Array,
                     cfg: ModelConfig, comm: CommContext, *,
                     capacity: int, sideband: Dict[str, Array],
                     use_kernel: bool = False) -> ExchangePlan:
    """Bind fresh routing onto a cached static plan template — the
    zero-planning serving path (DESIGN.md §9).

    ``template`` is a shape-keyed :class:`ExchangePlan` from a
    :class:`~repro.plan.cache.PlanCache` (built ahead of time by
    ``build_plan_template`` — its traced fields are placeholders). This
    reuses every *static* decision (chunk schedule, pipelined flag,
    estimate) and fills only the per-request routing, exactly the traced
    arithmetic ``build_exchange_plan`` performs in vanilla mode — so the
    executed forward is bit-identical to the uncached path while no
    planning (chunk search, pricing, objectives) runs per request.
    Templates are vanilla- or decode-mode only: serving prompts are
    never re-homed and never condensed (and ``build_exchange_plan``
    forces condensation off for ``mode="decode"``, so a decode template
    binds routing through the identical arithmetic).
    """
    m = cfg.moe
    T, d = xn.shape
    n_seq = sideband["seq_len"].shape[0]
    S = T // n_seq
    E = m.num_experts
    M = comm.size()
    E_local = E // M
    my = comm.index()
    C = capacity
    assert template.mode in ("vanilla", "decode") and not template.migrate \
        and not template.condense, (template.mode, template.migrate,
                                    template.condense)
    assert template.capacity == C and template.chunks.capacity == C, \
        (template.capacity, template.chunks, C)
    expert_idx, gate_w = gate.expert_idx, gate.gate_weights

    pos_in_seq = jnp.tile(jnp.arange(S, dtype=jnp.int32)[None], (n_seq, 1))
    token_valid = (pos_in_seq < sideband["seq_len"][:, None]).reshape(T)
    keep = jnp.tile(token_valid[:, None], (1, m.top_k))
    pos = dispatch_positions(expert_idx, keep, E)
    valid = keep & (pos < C)
    kept = jnp.sum(keep.astype(jnp.float32))
    d_drop = 1.0 - jnp.sum(valid.astype(jnp.float32)) / jnp.maximum(kept, 1.0)

    from repro.models.blocks import _dtype
    cdt = _dtype(cfg.compute_dtype)
    topo = comm.topology
    if topo is not None and topo.hierarchical and M > 1:
        row_bytes = float((d + 2) * jnp.dtype(cdt).itemsize)
        ib_flat, ib_dedup = comm_ledger.dispatch_node_ledger(
            expert_idx, valid, my, e_local=E_local, topo=topo,
            row_bytes=row_bytes)
        if comm.mode != "hier":
            ib_dedup = ib_flat
    else:
        ib_flat = ib_dedup = jnp.float32(0.0)

    z = jnp.float32(0.0)
    return ExchangePlan(
        mode=template.mode, migrate=False, condense=False,
        pipelined=template.pipelined, capacity=C, chunks=template.chunks,
        comm=comm, objective=template.objective,
        group_size=template.group_size,
        combine_slack=template.combine_slack, use_kernel=use_kernel,
        wire=template.wire, wire_dtype=template.wire_dtype,
        estimate=template.estimate,
        expert_idx=expert_idx, gate_weights=gate_w, positions=pos,
        valid=valid, aux_loss=gate.aux_loss, dispatch_drop=d_drop,
        condense_plan=identity_condense_plan(
            T, backend=template.condense_plan.backend),
        dest_global=my * n_seq + jnp.arange(n_seq, dtype=jnp.int32),
        traffic_before=z, traffic_after=z, inter_bytes_flat=ib_flat,
        inter_bytes_dedup=ib_dedup, signature=None, plans_built=z,
        plans_reused=jnp.float32(1.0), reuse_mismatch=z)


def instantiate_decode_plan(template: ExchangePlan, gate: GateOutput,
                            xn: Array, cfg: ModelConfig,
                            comm: CommContext, *, capacity: int,
                            sideband: Dict[str, Array],
                            use_kernel: bool = False) -> ExchangePlan:
    """Bind fresh routing onto a cached *decode* template (DESIGN.md
    §13) — the zero-planning steady-state decode path. The decode
    exchange is shape-static per batch slot (T = batch, S = 1), so one
    template covers every decode step of a serving run; this wrapper
    just asserts the template really is the decode one (a prefill
    template bound to a decode shape would be a silent cache-key bug)."""
    assert template.mode == "decode", template.mode
    return instantiate_plan(template, gate, xn, cfg, comm,
                            capacity=capacity, sideband=sideband,
                            use_kernel=use_kernel)


def _exchange_sideband(sb: Dict[str, Array], dest_global: Array,
                       n_seq: int, M: int,
                       comm: CommContext) -> Dict[str, Array]:
    """Move per-sequence side info to new homes (bijection on slots)."""
    if M == 1:
        # permutation within the single device
        out = {}
        inv = jnp.zeros((n_seq,), jnp.int32).at[dest_global % n_seq].set(
            jnp.arange(n_seq, dtype=jnp.int32))
        for k, v in sb.items():
            out[k] = v[inv]
        return out
    out = {}
    dd = dest_global // n_seq
    ds = dest_global % n_seq
    for k, v in sb.items():
        buf = jnp.zeros((M, n_seq) + v.shape[1:], v.dtype)
        buf = buf.at[dd, ds].add(v)
        buf = comm.combine(buf)
        out[k] = jnp.sum(buf, axis=0)      # exactly-one-writer per slot
    return out
