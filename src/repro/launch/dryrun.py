"""Multi-pod dry-run: lower + compile every (architecture × input shape ×
mesh) combination with ShapeDtypeStruct stand-ins (no allocation), record
memory_analysis / cost_analysis / per-collective bytes for the roofline.

Usage:
    python -m repro.launch.dryrun --arch olmoe-1b-7b --shape train_4k
    python -m repro.launch.dryrun --arch ... --shape ... --multi-pod
    python -m repro.launch.dryrun --all [--jobs 6]   # orchestrate subprocesses
"""

import argparse
import json
import os
import re
import subprocess
import sys
import time
from pathlib import Path

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

# shared with hlo_analysis (ISSUE 9) — the two copies used to drift
from repro.comm.dtypes import DTYPE_BYTES as _DTYPE_BYTES

_COLLECTIVES = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    """Bytes of an HLO type string like 'bf16[128,48,514]{2,1,0}' or a
    tuple '(f32[2,3], s32[4])'."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip()])
    return 0


def parse_collectives(hlo_text: str):
    """Sum result-operand bytes of every collective op in optimized HLO."""
    out = {c: {"bytes": 0, "count": 0, "ops": []} for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        if " = " not in ls:
            continue
        lhs, rhs = ls.split(" = ", 1)
        for c in _COLLECTIVES:
            # match the op name at the start of the rhs expression,
            # e.g. "bf16[...] all-to-all(" — not fused-computation refs
            m = re.match(r"^((?:\([^)]*\))|(?:[\w\[\]{},: ]+?))\s+"
                         + re.escape(c) + r"(-start|-done)?\(", rhs)
            if m:
                if m.group(2) == "-done":
                    continue       # counted at -start
                b = _shape_bytes(m.group(1))
                g = _group_size(ls)
                out[c]["bytes"] += b
                out[c]["count"] += 1
                if len(out[c]["ops"]) < 40:
                    out[c]["ops"].append({"bytes": b, "groups": g})
                break
    return out


def comm_traffic_ledger(cfg, shape, mesh, *, nodes: int = 0,
                        exec_chunks: int = 0, plan_reuse: str = "off",
                        similarity_backend: str = "exact",
                        lsh_bits: int = 8, condense_reuse: str = "off",
                        hier_dedup: str = "off",
                        wire_dtype: str = "f32",
                        condense_group: int = 128,
                        calibration=None,
                        autotune_applied: bool = False):
    """Analytic per-step dispatch traffic split by link tier (DESIGN.md §5)
    plus the modeled compute/communication overlap (§6).

    One :func:`repro.plan.estimate_exchange` call per condensation rate
    bucket — the SAME per-phase estimate the plan builder attaches to
    every :class:`~repro.plan.ExchangePlan` (the ledger reports plan
    numbers, it does not recompute them): bytes a flat all-to-all ships
    across nodes vs. the hierarchical path after per-node dedup, and the
    pipelined MoE-sublayer time — at exactly ``exec_chunks`` chunks when
    the run executed a pipeline, else at the 1..16 planning optimum
    (dispatch and combine priced on the hier bytes, expert FFN on the
    peak-FLOP roofline). On a flat mesh the ledger prices a hypothetical
    ``nodes``-way split of the model axis (default 4) — the planning
    number for moving to a hierarchical deployment.

    ``calibration`` (a ``repro.obs.calibrate.Calibration``) swaps every
    hand-set pricing constant for the measured fit: link bandwidths and
    latencies (via ``Calibration.topology``), the per-chunk pipeline
    overhead, the FFN roofline, and the planning/similarity step costs.
    The returned JSON carries ``schema_version`` (see
    ``repro.obs.metrics.COMM_LEDGER_SCHEMA_VERSION``); the golden-schema
    test pins its key sets."""
    from repro import comm as rcomm
    from repro.core.moe_layer import capacity_for
    from repro.launch.mesh import (DCN_BW, ICI_BW, PEAK_FLOPS_BF16,
                                   topology_for_mesh)
    from repro.plan import estimate_exchange
    from repro.sched import plan_chunks
    names = tuple(mesh.axis_names)
    if "node" in names:
        topo = topology_for_mesh(mesh)
    else:
        M = dict(zip(names, mesh.devices.shape)).get("model", 1)
        nodes = nodes or min(4, M)
        if M % nodes or M // nodes < 1:
            return None
        topo = rcomm.Topology(nodes, M // nodes,
                              intra_bw=ICI_BW, inter_bw=DCN_BW)
    if not topo.hierarchical or not cfg.uses_moe:
        return None
    from repro.obs.metrics import COMM_LEDGER_SCHEMA_VERSION
    if calibration is not None:
        topo = calibration.topology(topo)
    peak_flops = (calibration.ffn_speed if calibration is not None
                  else PEAK_FLOPS_BF16)
    est_kw = (calibration.estimate_kwargs() if calibration is not None
              else {})
    tokens = shape.global_batch * shape.seq_len
    k = cfg.moe.top_k
    out = {"schema_version": COMM_LEDGER_SCHEMA_VERSION,
           "calibration": (calibration.key if calibration is not None
                           else None),
           "topology": {"nodes": topo.num_nodes,
                        "devices_per_node": topo.devices_per_node,
                        "bw_ratio": topo.bw_ratio},
           "dedup_factor": rcomm.expected_dedup_factor(k, topo),
           "buckets": {}}
    for r in (0.0, 0.25, 0.5):
        # dispatch ≈ combine on the hier bytes; expert FFN at the bf16
        # roofline (or the measured fit) spread over the expert shards
        ffn_flops = (tokens * (1.0 - r) * k * 4 * cfg.d_model
                     * cfg.moe.d_ff * cfg.num_layers)
        ffn_ms = ffn_flops / (peak_flops * topo.num_devices) * 1e3
        if exec_chunks > 0:      # report the executed configuration,
            # with the executor's own capacity clipping (plan_chunks
            # caps the chunk count at this bucket's capacity / 8)
            cap = capacity_for(cfg.moe, tokens // mesh.devices.size,
                               cfg.moe.num_experts, rate=r)
            chunks = plan_chunks(cap, exec_chunks).n_chunks
        else:                    # planning search
            chunks = None
        est = estimate_exchange(tokens, k, cfg.d_model, topo=topo,
                                r_cond=r, num_layers=cfg.num_layers,
                                ffn_ms=ffn_ms, chunks=chunks,
                                wire_dtype=wire_dtype, **est_kw)
        out["buckets"][str(r)] = {
            "flat": {"intra_bytes": est.flat_intra_dispatch_bytes,
                     "inter_bytes": est.flat_inter_dispatch_bytes,
                     "time_s": est.flat_dispatch_ms / 1e3},
            "hier": {"intra_bytes": est.intra_dispatch_bytes,
                     "inter_bytes": est.inter_dispatch_bytes,
                     "time_s": est.dispatch_ms / 1e3},
            "overlap": {"ffn_ms": est.ffn_ms, "sync_ms": est.sync_ms,
                        "pipelined_ms": est.overlap_ms,
                        "chunks": est.chunks,
                        "speedup": est.speedup},
        }

    # ---- wire precision ledger (DESIGN.md §14) ---------------------------
    # The bucket byte/time fields above are already priced at this wire
    # dtype (estimate_exchange scales bytes_per_el by 1/wire_precision);
    # this section records the dtype and the exact per-row arithmetic so
    # a reader can undo or cross-check the scaling. bytes_per_el 4
    # matches estimate_exchange's default compute itemsize.
    from repro.comm import dtypes as wire_dtypes
    # Per-execution-mode shipped inter-node bytes (schema v6): the dedup
    # wire is universal (DESIGN.md §15), so vanilla / migrate / pipelined
    # all ship the per-node-deduplicated payload when it is on — the
    # three fields are equal by construction and exist so a reader (and
    # the golden-schema test) can see the mode scope is closed, not
    # implied. Dispatch bytes are mode-independent (experts never move),
    # which is why one number covers all three.
    b0w = out["buckets"]["0.0"]
    shipped = (b0w["hier"]["inter_bytes"] if hier_dedup == "on"
               else b0w["flat"]["inter_bytes"])
    out["wire"] = {
        "dtype": wire_dtype,
        "precision": wire_dtypes.wire_precision(cfg.d_model, wire_dtype, 4),
        "row_bytes": wire_dtypes.wire_row_bytes(cfg.d_model, wire_dtype, 4),
        "row_bytes_f32": (cfg.d_model + 2) * 4,
        "scale_block": wire_dtypes.SCALE_BLOCK,
        "shipped_vanilla_bytes": shipped,
        "shipped_migrate_bytes": shipped,
        "shipped_pipelined_bytes": shipped,
    }

    # ---- plan-reuse ledger (DESIGN.md §9) --------------------------------
    # Modeled under stable routing (the regime reuse exists for): with
    # plan_reuse on, one full replan per forward seeds the carried plan
    # and every later MoE sublayer revalidates instead of replanning.
    from repro.plan import estimate_planning_ms, estimate_revalidate_ms
    n_moe = sum(1 for i in range(cfg.num_layers)
                if cfg.ffn_kind(i) == "moe")
    M = topo.num_devices
    # migrate-mode training shards the batch over ALL mesh axes (the
    # planner only runs when seq_axis is None; see dist.make_dist), so
    # per-device n_seq is global_batch / mesh size and the planner sees
    # M * n_seq global slots
    n_seq_local = max(1, shape.global_batch // mesh.devices.size)
    n_slots = M * n_seq_local
    built = n_moe if plan_reuse == "off" else min(1, n_moe)
    reused = n_moe - built
    plan_ms = (estimate_planning_ms(n_slots, M,
                                    step_us=calibration.plan_step_us)
               if calibration is not None
               else estimate_planning_ms(n_slots, M))
    reval_ms = estimate_revalidate_ms(n_slots, M)
    # "always" trusts the carry without the signature compare, so it
    # pays no revalidation cost; "signature" checks every reused layer
    checks = reused if plan_reuse == "signature" else 0
    out["plan_reuse"] = {
        "mode": plan_reuse,
        "moe_sublayers": n_moe,
        "n_slots": n_slots,
        "plans_built_per_step": built,
        "plans_reused_per_step": reused,
        "revalidation_mismatches": 0,      # stable-routing model
        "planning_ms_per_plan": plan_ms,
        "revalidate_ms_per_check": reval_ms,
        "planning_ms_saved_per_step": reused * plan_ms
        - checks * reval_ms,
    }

    # ---- condensation ledger (DESIGN.md §10) -----------------------------
    # Per-backend measured-pair model (uniform first-block routing), the
    # dedup-wire bytes (modeled inter_bytes_dedup == shipped when the
    # wire is on — the executor asserts the traced equality), and the
    # condense-plan build/reuse counters under the same stable-routing
    # model as plan_reuse above.
    from repro.condense import expected_measured_pairs
    from repro.plan import estimate_similarity_ms
    G = min(condense_group, shape.seq_len)
    tokens_l = max(1, tokens // mesh.devices.size)   # per-device groups
    pairs = {b: expected_measured_pairs(
        tokens_l, G, cfg.moe.num_experts, backend=b, lsh_bits=lsh_bits)
        * mesh.devices.size
        for b in ("exact", "lsh")}
    # one build runs per device in parallel: price the per-device share
    sim_kw = ({"speed": calibration.sim_speed}
              if calibration is not None else {})
    sim_ms = {b: estimate_similarity_ms(p / mesh.devices.size,
                                        cfg.d_model, **sim_kw)
              for b, p in pairs.items()}
    b0 = out["buckets"]["0.0"]
    c_built = n_moe if condense_reuse == "off" else min(1, n_moe)
    c_reused = n_moe - c_built
    out["condensation"] = {
        "backend": similarity_backend,
        "group_size": G,
        "lsh_bits": lsh_bits,
        "measured_pairs_per_step": pairs,
        "similarity_ms_per_build": sim_ms,
        "dedup_wire": {
            "enabled": hier_dedup == "on",
            "modeled_inter_bytes": b0["hier"]["inter_bytes"],
            "flat_inter_bytes": b0["flat"]["inter_bytes"],
            "shipped_inter_bytes": (b0["hier"]["inter_bytes"]
                                    if hier_dedup == "on" else
                                    b0["flat"]["inter_bytes"]),
        },
        "condense_plan": {
            "mode": condense_reuse,
            "built_per_step": c_built,
            "reused_per_step": c_reused,
            "similarity_ms_saved_per_step":
                c_reused * sim_ms[similarity_backend],
        },
    }

    # ---- decode ledger (DESIGN.md §13) -----------------------------------
    # The serving decode step on this fabric: one [B, d_model] combine
    # all-reduce per MoE sublayer (moe_decode_allreduce — no all-to-all
    # at decode) plus the shared-expert FFN, and what the
    # "decode_overlap" exec mode saves by issuing the psum concurrently
    # with those matmuls. Modeled with the SAME sched.cost functions the
    # autotune grid prices the decode_ms term with; archs without shared
    # experts (shared_ffn_ms 0) show speedup 1.0 — there is nothing to
    # hide the wire behind.
    from repro.sched.cost import decode_combine_ms, decode_step_ms
    dec_tokens = shape.global_batch          # one live token per sequence
    dec_combine = decode_combine_ms(dec_tokens, cfg.d_model, topo)
    dec_shared = (dec_tokens * 4.0 * cfg.d_model * cfg.moe.d_ff
                  * cfg.moe.num_shared_experts / peak_flops * 1e3)
    dec_sync = decode_step_ms(combine_ms=dec_combine,
                              shared_ffn_ms=dec_shared,
                              overlap=False) * n_moe
    dec_ovl = decode_step_ms(combine_ms=dec_combine,
                             shared_ffn_ms=dec_shared,
                             overlap=True) * n_moe
    out["decode"] = {
        "tokens": dec_tokens,
        "combine_ms": dec_combine,
        "shared_ffn_ms": dec_shared,
        "sync_ms": dec_sync,
        "overlap_ms": dec_ovl,
        "modeled_speedup": dec_sync / max(dec_ovl, 1e-12),
    }

    # ---- autotune ledger (DESIGN.md §12) ---------------------------------
    # The calibration-driven knob search over THIS ledger's topology and
    # pricing constants: chosen config + modeled step time vs the repo
    # defaults. `applied` records whether the run actually resolved a
    # TunedConfig into its compiled LuffyConfig (--autotune) — the
    # section itself is always modeled, so every dryrun artifact reports
    # what tuning WOULD buy on its fabric. Defaults are always in the
    # grid, so modeled_step_ms <= default_step_ms by construction
    # (swept by benchmarks/fig_autotune.py).
    from repro.obs.autotune import autotune_config
    tuned = autotune_config(
        topo=topo, tokens=tokens, top_k=k, d_model=cfg.d_model,
        d_ff=cfg.moe.d_ff, num_layers=cfg.num_layers,
        n_moe=max(1, n_moe), n_slots=n_slots,
        num_experts=cfg.moe.num_experts,
        mesh_devices=mesh.devices.size, group_size=G,
        plan_reuse=plan_reuse, condense_reuse=condense_reuse,
        calib=calibration, ffn_speed=peak_flops)
    out["autotune"] = {
        "applied": bool(autotune_applied),
        "key": tuned.key,
        "knobs": dict(tuned.knobs),
        "modeled_step_ms": tuned.modeled_step_ms,
        "default_step_ms": tuned.default_step_ms,
        "modeled_savings_ms": tuned.modeled_savings_ms,
        "candidates": tuned.candidates,
    }
    return out


def run_pair(arch: str, shape_name: str, multi_pod: bool,
             out_path: Path, *, luffy_on: bool = True,
             bucket: int = 0, variant: str = "baseline",
             nodes: int = 0, exec_mode: str = None,
             pipeline_chunks: int = None, plan_objective: str = None,
             plan_reuse: str = "off", similarity_backend: str = None,
             lsh_bits: int = None, condense_reuse: str = "off",
             hier_dedup: str = None, wire_dtype: str = None,
             calibration_path: str = "",
             autotune_dir: str = "", autotune_force: bool = False):
    import jax
    import jax.numpy as jnp
    from repro import optim, serve_lib, train_lib
    from repro.config import (SHAPES, LuffyConfig, OptimConfig,
                              resolve_pipeline_chunks)
    from repro.configs import get_config
    from repro.dist import make_dist
    from repro.launch.mesh import (PEAK_FLOPS_BF16, make_production_mesh,
                                   topology_for_mesh)
    from repro.obs import autotune as obs_at

    t0 = time.time()
    cfg = get_config(arch)
    calibration = None
    if calibration_path:
        from repro.obs.calibrate import Calibration
        calibration = Calibration.from_json(
            Path(calibration_path).read_text())
        if calibration is None:
            raise ValueError(
                f"unreadable calibration artifact: {calibration_path} "
                "(wrong magic, schema drift, or malformed)")
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod, nodes=nodes)

    # knob resolution (DESIGN.md §12): explicit args > tuned artifact
    # (--autotune) > historical defaults. comm_mode stays structural —
    # it is pinned by the mesh axes the --nodes split built.
    cli = {"exec_mode": exec_mode, "pipeline_chunks": pipeline_chunks,
           "plan_objective": plan_objective,
           "similarity_backend": similarity_backend,
           "lsh_bits": lsh_bits, "hier_dedup": hier_dedup,
           "wire_dtype": wire_dtype}
    explicit = {k for k, v in cli.items() if v is not None}
    comm_mode = "hier" if nodes > 1 else "flat"
    tuned = None
    if autotune_dir and cfg.uses_moe:
        at_topo = topology_for_mesh(mesh)
        n_moe_l = sum(1 for i in range(cfg.num_layers)
                      if cfg.ffn_kind(i) == "moe")
        n_seq_l = max(1, shape.global_batch // mesh.devices.size)
        tuned = obs_at.run_autotune(
            topo=at_topo, out_dir=autotune_dir, force=autotune_force,
            tokens=shape.global_batch * shape.seq_len,
            top_k=cfg.moe.top_k, d_model=cfg.d_model,
            d_ff=cfg.moe.d_ff, num_layers=cfg.num_layers,
            n_moe=max(1, n_moe_l),
            n_slots=at_topo.num_devices * n_seq_l,
            num_experts=cfg.moe.num_experts,
            mesh_devices=mesh.devices.size,
            group_size=min(128, shape.seq_len), plan_reuse=plan_reuse,
            condense_reuse=condense_reuse, calib=calibration,
            ffn_speed=PEAK_FLOPS_BF16)
        print(f"autotune {tuned.key}: {tuned.knobs} modeled "
              f"{tuned.modeled_step_ms:.3f}ms vs default "
              f"{tuned.default_step_ms:.3f}ms")
    knobs = dict(obs_at.DEFAULT_KNOBS)
    knobs["pipeline_chunks"] = None    # sentinel: resolve by objective
    if tuned is not None:
        knobs.update({k: v for k, v in tuned.knobs.items()
                      if k not in explicit and k != "comm_mode"})
    knobs.update({k: v for k, v in cli.items() if v is not None})
    if "hier_dedup" not in explicit and knobs["hier_dedup"] == "on" \
            and comm_mode != "hier":
        knobs["hier_dedup"] = "off"   # dedup wire needs hier comm; it
                                      # is otherwise universal (§15)
    if knobs["pipeline_chunks"] is None:
        knobs["pipeline_chunks"] = resolve_pipeline_chunks(
            None, knobs["plan_objective"])
    exec_mode = knobs["exec_mode"]
    pipeline_chunks = knobs["pipeline_chunks"]
    plan_objective = knobs["plan_objective"]
    similarity_backend = knobs["similarity_backend"]
    lsh_bits = knobs["lsh_bits"]
    hier_dedup = knobs["hier_dedup"]
    wire_dtype = knobs["wire_dtype"]

    from repro.models.model import build_model
    mesh_tag = "x".join(str(d) for d in mesh.devices.shape)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
           "variant": variant, "exec_mode": exec_mode,
           "plan_objective": plan_objective, "plan_reuse": plan_reuse,
           "autotuned": tuned is not None,
           "status": "unknown"}

    if shape_name == "long_500k" and not cfg.supports_long_decode:
        rec["status"] = "skipped"
        rec["reason"] = "full-attention arch; long_500k skipped (DESIGN.md)"
        out_path.write_text(json.dumps(rec, indent=1))
        print(f"SKIP {arch} {shape_name}")
        return rec

    dist = make_dist(mesh, shape.mode, shape.global_batch,
                     moe_arch=cfg.uses_moe)
    model = build_model(cfg)
    pstruct = model.init_struct()
    pspecs = model.param_pspecs(dist, pstruct)

    def with_sharding(struct, specs):
        return jax.tree.map(
            lambda s, p: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=dist.sharding(p)),
            struct, specs,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))

    params_in = with_sharding(pstruct, pspecs)
    luffy = LuffyConfig(
        enable_condensation=luffy_on and cfg.uses_moe,
        enable_migration=luffy_on and cfg.uses_moe,
        comm_mode=comm_mode,
        exec_mode=exec_mode, pipeline_chunks=pipeline_chunks,
        plan_objective=plan_objective, plan_reuse=plan_reuse,
        similarity_backend=similarity_backend, lsh_bits=lsh_bits,
        condense_reuse=condense_reuse, hier_dedup=hier_dedup,
        wire_dtype=wire_dtype)

    if shape.mode == "train":
        # 100B+ models: full f32 Adam moments cannot fit 16GB/chip even at
        # maximal sharding — use Adafactor (production choice; DESIGN.md)
        ocfg = OptimConfig(name="adafactor"
                           if cfg.param_count() > 1e11 else "adamw")
        rec["optimizer"] = ocfg.name
        ostruct = jax.eval_shape(
            lambda p: optim.init_opt_state(p, ocfg), pstruct)
        from jax.sharding import PartitionSpec as P
        mu_specs, nu_specs = model.opt_moment_pspecs(dist, ocfg, pstruct)
        opt_in = optim.OptState(
            jax.ShapeDtypeStruct((), jnp.int32,
                                 sharding=dist.sharding(P())),
            with_sharding(ostruct.mu, mu_specs),
            with_sharding(ostruct.nu, nu_specs))
        lstate_in = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=dist.sharding(P())),
            jax.eval_shape(train_lib.init_luffy_state))
        batch_in = model.input_specs(shape, dist)
        if cfg.uses_moe:
            cap = train_lib.capacity_for_bucket(cfg, shape, dist, luffy,
                                                bucket)
        else:
            cap = 8
        step = train_lib.make_train_step(cfg, luffy, ocfg, dist, cap,
                                         param_pspecs=pspecs)
        fn = jax.jit(step, donate_argnums=(0, 1))
        lowered = fn.lower(params_in, opt_in, lstate_in, batch_in)
    elif shape.mode == "prefill":
        batch_in = model.input_specs(shape, dist)

        def pf(params, batch):
            return model.prefill(
                params, batch["tokens"], shape.seq_len, luffy=luffy,
                dist=dist, prefix=batch.get("prefix"),
                enc_input=batch.get("enc_input"))[0]

        lowered = jax.jit(pf).lower(params_in, batch_in)
    else:  # decode
        cache_in, _ = model.cache_specs(shape, dist)
        batch_in = model.input_specs(shape, dist)

        def dec(params, cache, batch):
            return model.decode_step(params, cache, batch["tokens"],
                                     luffy=luffy, dist=dist)

        lowered = jax.jit(dec, donate_argnums=(1,)).lower(
            params_in, cache_in, batch_in)

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):     # old jax: list of per-program dicts
        ca = ca[0] if ca else {}
    hlo = compiled.as_text()
    coll = parse_collectives(hlo)
    # loop-corrected analysis: cost_analysis counts while (scan) bodies
    # once; our models scan over layer groups (see hlo_analysis.py)
    from repro.launch import hlo_analysis
    corrected = hlo_analysis.analyze(hlo)

    # Analytic per-device static memory (exact, backend-independent):
    # NOTE the CPU backend emulates bf16 dots by materializing f32 operand
    # copies, inflating temp_bytes for bf16 archs vs real TPU (DESIGN.md).
    def sharded_bytes(struct, specs):
        import numpy as _np
        from jax.sharding import PartitionSpec as _P
        ax_size = dict(zip(mesh.axis_names, mesh.devices.shape))
        leaves = jax.tree.leaves(struct, is_leaf=lambda x: isinstance(
            x, jax.ShapeDtypeStruct))
        sl = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, _P))
        out = 0
        for leaf, spec in zip(leaves, sl):
            factor = 1
            for entry in (spec or ()):
                if entry is None:
                    continue
                for ax in (entry if isinstance(entry, tuple) else (entry,)):
                    factor *= ax_size[ax]
            out += int(_np.prod(leaf.shape)) * leaf.dtype.itemsize // factor
        return out

    analytic = {"param_bytes_per_device": sharded_bytes(pstruct, pspecs)}
    if shape.mode == "train":
        analytic["opt_moment_bytes_per_device"] = (
            sharded_bytes(ostruct.mu, mu_specs)
            + sharded_bytes(ostruct.nu, nu_specs))
    if shape.mode == "decode":
        analytic["cache_bytes_per_device"] = sharded_bytes(
            cache_in, model.cache_specs(shape, dist)[1])

    rec.update({
        "status": "ok",
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "num_devices": mesh.devices.size,
        "memory": {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "generated_code_bytes": int(ma.generated_code_size_in_bytes),
        },
        "cost": {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        },
        "collectives": {k: {"bytes": v["bytes"], "count": v["count"],
                            "ops": v["ops"]}
                        for k, v in coll.items()},
        "corrected": {
            "flops": corrected["flops"],
            "bytes_touched": corrected["bytes_touched"],
            "collectives": {k: {"bytes": v["bytes"], "count": v["count"],
                                "wire_bytes": v["wire_bytes"],
                                "wire_bytes_f32": v["wire_bytes_f32"]}
                            for k, v in corrected["collectives"].items()},
            "loop_multipliers": corrected["loop_multipliers"],
        },
        "model": {
            "params": cfg.param_count(),
            "active_params": cfg.active_param_count(),
        },
        "analytic": analytic,
        "comm_ledger": (comm_traffic_ledger(
            cfg, shape, mesh, nodes=nodes,
            exec_chunks=(pipeline_chunks if exec_mode == "pipeline"
                         else 0), plan_reuse=plan_reuse,
            similarity_backend=similarity_backend, lsh_bits=lsh_bits,
            condense_reuse=condense_reuse, hier_dedup=hier_dedup,
            wire_dtype=wire_dtype,
            condense_group=luffy.condense_group,
            calibration=calibration,
            autotune_applied=tuned is not None)
                        if shape.mode == "train" else None),
    })
    out_path.parent.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(rec, indent=1))
    tot_coll = sum(v["bytes"] for v in coll.values())
    print(f"OK {arch} {shape_name} {rec['mesh']} [{variant}] "
          f"lower={t_lower:.0f}s compile={t_compile:.0f}s "
          f"temp={ma.temp_size_in_bytes/2**30:.2f}GiB "
          f"flops={ca.get('flops', 0):.3g} coll={tot_coll/2**20:.1f}MiB")
    return rec


def pair_list():
    from repro.config import SHAPES
    from repro.configs import ARCHS, get_config
    pairs = []
    for arch in ARCHS[:10]:                 # the 10 assigned archs
        for shape in SHAPES:
            pairs.append((arch, shape))
    # the paper's own models, at their evaluation context (training)
    for arch in ARCHS[10:]:
        pairs.append((arch, "train_4k"))
    return pairs


def orchestrate(jobs: int, multi_pod_also: bool = True,
                only_missing: bool = True):
    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    work = []
    for arch, shape in pair_list():
        for mp in ([False, True] if multi_pod_also else [False]):
            mesh_tag = "2x16x16" if mp else "16x16"
            out = ARTIFACTS / f"{arch}__{shape}__{mesh_tag}.json"
            if only_missing and out.exists():
                try:
                    if json.loads(out.read_text()).get("status") in (
                            "ok", "skipped"):
                        continue
                except Exception:
                    pass
            work.append((arch, shape, mp, out))
    print(f"{len(work)} dry-run jobs, {jobs} parallel")
    procs = []
    while work or procs:
        while work and len(procs) < jobs:
            arch, shape, mp, out = work.pop(0)
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--out", str(out)]
            if mp:
                cmd.append("--multi-pod")
            logf = open(str(out) + ".log", "w")
            procs.append((subprocess.Popen(
                cmd, stdout=logf, stderr=subprocess.STDOUT,
                env={**os.environ, "PYTHONPATH": "src",
                     "JAX_PLATFORMS": "cpu"},
                cwd=str(ARTIFACTS.parents[1])), arch, shape, mp, out, logf,
                time.time()))
        still = []
        for p, arch, shape, mp, out, logf, t0 in procs:
            if p.poll() is None:
                if time.time() - t0 > 3600:
                    p.kill()
                    print(f"TIMEOUT {arch} {shape} mp={mp}")
                else:
                    still.append((p, arch, shape, mp, out, logf, t0))
            else:
                logf.close()
                tag = "2x16x16" if mp else "16x16"
                ok = out.exists()
                print(f"[{time.strftime('%H:%M:%S')}] done {arch} {shape} "
                      f"{tag} rc={p.returncode} artifact={ok}")
        procs = still
        time.sleep(3)
    print("orchestration complete")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=6)
    ap.add_argument("--bucket", type=int, default=0)
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--no-luffy", action="store_true")
    ap.add_argument("--nodes", type=int, default=0,
                    help="hierarchical mesh: split the model axis into "
                         "this many nodes (comm_mode=hier)")
    ap.add_argument("--exec-mode",
                    choices=["sync", "pipeline", "decode_overlap"],
                    default=None,
                    help="MoE execution schedule: strict order, chunked "
                         "pipeline with overlap (DESIGN.md §6), or the "
                         "decode combine/shared-FFN overlap (DESIGN.md "
                         "§13 — prices like sync on the train path; "
                         "default sync)")
    ap.add_argument("--pipeline-chunks", type=int, default=None,
                    help="capacity chunks for --exec-mode pipeline "
                         "(default 4; under --plan-objective overlap "
                         "the estimate search picks the count)")
    ap.add_argument("--plan-objective", default=None,
                    choices=["traffic", "overlap", "replicate"],
                    help="migration planner objective (DESIGN.md §7; "
                         "\"replicate\" adds intra-node hot-expert "
                         "replicas, DESIGN.md §15; default traffic)")
    ap.add_argument("--plan-reuse", default="off",
                    choices=["off", "signature", "always"],
                    help="cross-layer plan reuse; also selects the "
                         "comm_ledger plan_reuse section's modeled "
                         "mode (DESIGN.md §9)")
    ap.add_argument("--similarity-backend", default=None,
                    choices=["exact", "lsh"],
                    help="condensation similarity backend "
                         "(repro.condense.backends, DESIGN.md §10; "
                         "default exact)")
    ap.add_argument("--lsh-bits", type=int, default=None,
                    help="projections per LSH bucket code (default 8)")
    ap.add_argument("--condense-reuse", default="off",
                    choices=["off", "signature", "always"],
                    help="cross-layer condense-plan reuse; also selects "
                         "the comm_ledger condensation section's "
                         "modeled mode (DESIGN.md §10)")
    ap.add_argument("--hier-dedup", default=None, choices=["off", "on"],
                    help="deduplicated hier wire format "
                         "(repro.condense.wire; needs --nodes > 1; "
                         "default off)")
    ap.add_argument("--wire-dtype", default=None,
                    choices=["f32", "bf16", "f8e4m3"],
                    help="precision activation rows ship at on node-"
                         "crossing exchange hops (DESIGN.md §14); the "
                         "comm_ledger's wire section and bucket bytes "
                         "are priced at it (default f32)")
    ap.add_argument("--autotune", default="",
                    help="TunedConfig artifact dir (repro.obs.autotune): "
                         "fill every knob the CLI left unset from the "
                         "tuned artifact for this mesh's topology "
                         "(explicit flags always override; DESIGN.md "
                         "§12)")
    ap.add_argument("--autotune-force", action="store_true",
                    help="re-run the autotune search even when a valid "
                         "artifact exists")
    ap.add_argument("--calibration", default="",
                    help="path to a repro.obs.calibrate artifact "
                         "(*.calib.json): price the comm_ledger with "
                         "the measured fit instead of the hand-set "
                         "constants")
    ap.add_argument("--metrics-json", default="",
                    help="also append the flattened comm_ledger as one "
                         "unified metrics record (repro.obs.metrics "
                         "JSONL) to this path")
    args = ap.parse_args()
    # the production meshes are built from 512 placeholder host devices;
    # the flag must be in place before JAX starts its CPU backend
    import jax
    jax.config.update("jax_platforms", "cpu")
    os.environ["XLA_FLAGS"] = " ".join(filter(None, (
        os.environ.get("XLA_FLAGS"),
        "--xla_force_host_platform_device_count=512")))
    from repro.config import resolve_pipeline_chunks
    if args.all:
        orchestrate(args.jobs)
        return
    # knob resolution happens in run_pair (None = "not set", so
    # --autotune can fill it); the artifact tag reflects only what the
    # CLI pinned explicitly
    mesh_tag = "2x16x16" if args.multi_pod else "16x16"
    if args.nodes > 1:
        mesh_tag += f"__hier{args.nodes}"
    if args.exec_mode == "pipeline":
        chunks = (args.pipeline_chunks if args.pipeline_chunks is not None
                  else resolve_pipeline_chunks(
                      None, args.plan_objective or "traffic"))
        mesh_tag += f"__pipe{chunks}"
    if args.plan_objective not in (None, "traffic"):
        mesh_tag += f"__{args.plan_objective}"
    if args.plan_reuse != "off":
        mesh_tag += f"__reuse-{args.plan_reuse}"
    if args.similarity_backend not in (None, "exact"):
        mesh_tag += f"__{args.similarity_backend}"
    if args.condense_reuse != "off":
        mesh_tag += f"__creuse-{args.condense_reuse}"
    if args.hier_dedup == "on":
        mesh_tag += "__dedup"
    if args.wire_dtype not in (None, "f32"):
        mesh_tag += f"__wd-{args.wire_dtype}"
    if args.autotune:
        mesh_tag += "__autotuned"
    out = Path(args.out) if args.out else \
        ARTIFACTS / f"{args.arch}__{args.shape}__{mesh_tag}.json"
    out.parent.mkdir(parents=True, exist_ok=True)
    try:
        rec = run_pair(args.arch, args.shape, args.multi_pod, out,
                       luffy_on=not args.no_luffy, bucket=args.bucket,
                       variant=args.variant, nodes=args.nodes,
                       exec_mode=args.exec_mode,
                       pipeline_chunks=args.pipeline_chunks,
                       plan_objective=args.plan_objective,
                       plan_reuse=args.plan_reuse,
                       similarity_backend=args.similarity_backend,
                       lsh_bits=args.lsh_bits,
                       condense_reuse=args.condense_reuse,
                       hier_dedup=args.hier_dedup,
                       wire_dtype=args.wire_dtype,
                       calibration_path=args.calibration,
                       autotune_dir=args.autotune,
                       autotune_force=args.autotune_force)
        if args.metrics_json and rec.get("comm_ledger"):
            from repro.obs import metrics as obs_metrics
            flat = obs_metrics.flatten("comm_ledger", rec["comm_ledger"])
            record = {"schema_version":
                      obs_metrics.METRICS_SCHEMA_VERSION,
                      "arch": args.arch, "shape": args.shape,
                      "mesh": rec["mesh"], "metrics": flat}
            obs_metrics.write_jsonl(args.metrics_json, record)
    except Exception as e:
        rec = {"arch": args.arch, "shape": args.shape, "mesh": mesh_tag,
               "variant": args.variant, "status": "error",
               "error": f"{type(e).__name__}: {e}"}
        out.write_text(json.dumps(rec, indent=1))
        raise


if __name__ == "__main__":
    main()
