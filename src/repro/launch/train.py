"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch olmoe-1b-7b \
        --steps 200 --reduced --mesh host --model-axis 4

``main(argv)`` is callable in-process (``chip_smoke.py`` drives it that
way) and returns the run's losses, step times and compile times.

Runs the full production stack: mesh + sharded params, LUFFY (adaptive
condensation threshold with host-side rate-bucket switching — one
compiled executable per bucket, cached), AdamW/Adafactor, checkpointing,
metrics logging. ``--mesh host`` builds a mesh over the visible devices
(CPU testing); ``--mesh production`` targets the 16×16 pod (dry-run
hardware only).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from pathlib import Path


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="moe-gpt2")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale variant of the arch (CPU)")
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--layers", type=int, default=None,
                    help="depth: with --reduced the smoke depth "
                         "(default 2); without it, cut the arch to this "
                         "many layers and keep every width")
    ap.add_argument("--experts", type=int, default=0,
                    help="override expert count (reduced mode)")
    ap.add_argument("--global-batch", type=int, default=0)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--mesh", choices=["host", "production", "none"],
                    default="host")
    ap.add_argument("--model-axis", type=int, default=4)
    # Tunable knobs default to None ("not set"): --autotune may fill
    # them, and anything the user typed explicitly always wins
    # (DESIGN.md §12). Unset knobs without --autotune fall back to the
    # historical defaults (flat/sync/traffic/exact/8/off).
    ap.add_argument("--comm-mode", choices=["flat", "hier"], default=None,
                    help="expert-parallel collectives: one flat all-to-all "
                         "or hierarchical two-phase (DESIGN.md §5; "
                         "default flat)")
    ap.add_argument("--nodes", type=int, default=0,
                    help="split the model axis into this many nodes "
                         "(builds a (node, local) mesh; required for "
                         "--comm-mode hier)")
    ap.add_argument("--inter-bw", type=float, default=0.0,
                    help="override cross-node bandwidth (bytes/s) for the "
                         "topology ledger / migration link costs")
    ap.add_argument("--exec-mode", choices=["sync", "pipeline"],
                    default=None,
                    help="MoE execution schedule: strict dispatch→FFN→"
                         "combine order, or chunked software pipeline "
                         "overlapping collectives with expert compute "
                         "(bit-identical; DESIGN.md §6; default sync)")
    ap.add_argument("--pipeline-chunks", type=int, default=None,
                    help="capacity chunks for --exec-mode pipeline "
                         "(clipped to capacity/8). Default: 4, except "
                         "under --plan-objective overlap where the "
                         "estimate search picks the count (0 = force "
                         "the planned count; DESIGN.md §9)")
    ap.add_argument("--plan-objective", default=None,
                    choices=["traffic", "overlap", "replicate"],
                    help="migration planner objective (DESIGN.md §7): "
                         "link-cost-weighted bytes, modeled exposed "
                         "(un-overlappable) time under the pipeline, or "
                         "traffic + intra-node hot-expert replication "
                         "(DESIGN.md §15; default traffic)")
    ap.add_argument("--plan-reuse", default="off",
                    choices=["off", "signature", "always"],
                    help="cross-layer migration-plan reuse (DESIGN.md "
                         "§9): replan every MoE sublayer, revalidate a "
                         "carried plan by routing signature, or trust "
                         "it unconditionally")
    ap.add_argument("--similarity-backend", default=None,
                    choices=["exact", "lsh"],
                    help="condensation similarity backend (DESIGN.md "
                         "§10): measure every §V-A uncertain pair, or "
                         "only LSH-bucket collisions (fewer measured "
                         "pairs for large groups; default exact)")
    ap.add_argument("--lsh-bits", type=int, default=None,
                    help="signed random projections per LSH bucket code "
                         "(default 8)")
    ap.add_argument("--condense-reuse", default="off",
                    choices=["off", "signature", "always"],
                    help="cross-layer condense-plan reuse (DESIGN.md "
                         "§10): rebuild similarity every MoE sublayer, "
                         "revalidate the carried rep map by primary-"
                         "expert signature, or trust it up to the age "
                         "bound")
    ap.add_argument("--condense-max-age", type=int, default=4,
                    help="staleness bound (sublayers) on a reused "
                         "condense plan (§V-A freshness)")
    ap.add_argument("--hier-dedup", default=None, choices=["off", "on"],
                    help="ship the per-node-deduplicated hier payload "
                         "(repro.condense.wire; needs --comm-mode hier, "
                         "works under every exec mode incl. migrate + "
                         "pipelined, DESIGN.md §15; default off)")
    ap.add_argument("--wire-dtype", default=None,
                    choices=["f32", "bf16", "f8e4m3"],
                    help="precision activation rows ship at when they "
                         "cross a node boundary (DESIGN.md §14): "
                         "identity wire, bf16 cast, or f8e4m3 with "
                         "per-32-element f32 scales. Frozen into the "
                         "exchange plan; compute stays at the compute "
                         "dtype (default f32)")
    ap.add_argument("--wire-error-feedback", action="store_true",
                    help="carry each token's wire quantization residual "
                         "into the next step's shipped payload "
                         "(DESIGN.md §15); no effect under --wire-dtype "
                         "f32")
    ap.add_argument("--no-condensation", action="store_true")
    ap.add_argument("--no-migration", action="store_true")
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-file", default="")
    ap.add_argument("--metrics-json", default="",
                    help="append one unified per-step metrics record "
                         "(repro.obs.metrics JSONL) per step to this "
                         "path")
    ap.add_argument("--trace", action="store_true",
                    help="step tracing (repro.obs.trace): fenced spans "
                         "around every jitted step plus one eager "
                         "exchange probe for the per-phase breakdown; "
                         "writes Chrome-trace JSON (see --trace-out) "
                         "and, beside it, the JAX profiler's trace "
                         "(<trace-out without .json>.profile/)")
    ap.add_argument("--trace-out", default="",
                    help="trace JSON path (implies --trace; default "
                         "trace.json)")
    ap.add_argument("--calibrate", default="",
                    help="calibration artifact dir (repro.obs.calibrate)"
                         ": load the fit for this topology+backend or "
                         "measure and persist one, then price links, "
                         "chunk overhead and the FFN roofline with it")
    ap.add_argument("--autotune", default="",
                    help="TunedConfig artifact dir (repro.obs.autotune): "
                         "load the tuned knob set for this topology+"
                         "backend or search and persist one, then fill "
                         "every knob the CLI left unset (explicit flags "
                         "always override; DESIGN.md §12)")
    ap.add_argument("--autotune-force", action="store_true",
                    help="re-run the autotune search even when a valid "
                         "artifact exists (overwrites it)")
    ap.add_argument("--autotune-refine", type=int, default=0,
                    help="after this many measured warmup steps, re-rank "
                         "the tuned top candidates under the measured/"
                         "modeled step-time ratio (online refinement; "
                         "0 = off)")
    ap.add_argument("--recalibrate-on-drift", action="store_true",
                    help="when the step-time drift detector fires "
                         "(repro.obs.monitor), re-measure the "
                         "calibration in place (force=True; needs "
                         "--calibrate; at most once per run)")
    ap.add_argument("--drift-tolerance", type=float, default=1.5,
                    help="drift detector tolerance: EWMA of measured/"
                         "expected step time outside [1/t, t] counts as "
                         "out-of-tolerance")
    ap.add_argument("--drift-k", type=int, default=5,
                    help="consecutive out-of-tolerance steps before the "
                         "drift detector fires")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from repro.launch.device import device_banner, enable_compile_cache
    device = device_banner()
    enable_compile_cache()
    from repro import checkpoint, optim, train_lib
    from repro.config import (LuffyConfig, OptimConfig, ShapeConfig,
                              reduced)
    from repro.configs import get_config
    from repro.data import SyntheticLM
    from repro.dist import DistContext, make_dist, single_device
    from repro.launch.mesh import (make_host_mesh, make_production_mesh,
                                   topology_for_mesh)
    from repro.models.model import build_model

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg, num_layers=args.layers or 2,
                      d_model=args.d_model,
                      max_experts=args.experts or 4,
                      seq_len_hint=args.seq_len)
    elif args.layers:
        print(f"depth cut: {args.arch} {cfg.num_layers} -> {args.layers} "
              f"layers (widths unchanged)")
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    gb = args.global_batch or (8 if args.reduced else 256)
    shape = ShapeConfig("train", args.seq_len, gb, "train")

    nodes = args.nodes
    if args.comm_mode == "hier" and nodes <= 1:
        nodes = 2                     # hier needs a (node, local) split
    mesh = topo = None
    if not (args.mesh == "none" or len(jax.devices()) == 1):
        mesh = (make_production_mesh(nodes=nodes)
                if args.mesh == "production"
                else make_host_mesh(model=args.model_axis, nodes=nodes))
        topo = topology_for_mesh(
            mesh, inter_bw=args.inter_bw or None)

    # measured cost-model fit (DESIGN.md §11): load or measure BEFORE the
    # dist context so migration link costs / the overlap model / the
    # ledger all price calibrated links
    calib = None
    if args.calibrate:
        from repro.obs import calibrate as obs_cal
        calib = obs_cal.run_calibration(mesh, topo, out_dir=args.calibrate)
        if topo is not None:
            topo = calib.topology(topo)
        print(f"calibration {calib.key}: "
              f"intra_bw={calib.intra_bw:.3g}B/s "
              f"inter_bw={calib.inter_bw:.3g}B/s "
              f"chunk_overhead={calib.chunk_overhead_ms:.3g}ms "
              f"ffn_speed={calib.ffn_speed:.3g}FLOP/s")

    # knob resolution (DESIGN.md §12): explicit CLI flags > tuned
    # artifact (--autotune) > historical defaults
    from repro.comm.topology import Topology
    from repro.obs import autotune as obs_at
    explicit = {k for k in obs_at.TUNABLE_KNOBS
                if getattr(args, k) is not None}
    n_moe = (sum(1 for i in range(cfg.num_layers)
                 if cfg.ffn_kind(i) == "moe") if cfg.uses_moe else 0)
    at_topo = topo if topo is not None else Topology.flat(1)
    tuned = None
    if args.autotune and cfg.uses_moe:
        tuned = obs_at.run_autotune(
            topo=at_topo, out_dir=args.autotune,
            force=args.autotune_force,
            tokens=gb * args.seq_len, top_k=cfg.moe.top_k,
            d_model=cfg.d_model, d_ff=cfg.moe.d_ff,
            num_layers=max(1, n_moe), n_moe=max(1, n_moe),
            n_slots=gb, num_experts=cfg.moe.num_experts,
            mesh_devices=mesh.devices.size if mesh is not None else 1,
            group_size=min(128, args.seq_len),
            plan_reuse=args.plan_reuse,
            condense_reuse=args.condense_reuse, calib=calib)
        print(f"autotune {tuned.key}: {tuned.knobs} "
              f"modeled {tuned.modeled_step_ms:.3f}ms vs default "
              f"{tuned.default_step_ms:.3f}ms "
              f"({tuned.candidates} candidates, "
              f"calibrated={tuned.calibrated})")
    knobs = dict(obs_at.DEFAULT_KNOBS)
    knobs["pipeline_chunks"] = None    # sentinel: resolve by objective
    if tuned is not None:
        knobs.update({k: v for k, v in tuned.knobs.items()
                      if k not in explicit})
    for k in explicit:
        knobs[k] = getattr(args, k)
    if "hier_dedup" not in explicit and knobs["hier_dedup"] == "on" \
            and knobs["comm_mode"] != "hier":
        knobs["hier_dedup"] = "off"   # dedup wire needs hier comm; it
                                      # is otherwise universal (§15)
    from repro.config import resolve_pipeline_chunks
    if knobs["pipeline_chunks"] is None:
        # objective-aware chunk count (DESIGN.md §9): under the
        # "overlap" objective the estimate search picks n_chunks
        knobs["pipeline_chunks"] = resolve_pipeline_chunks(
            None, knobs["plan_objective"])

    if mesh is None:
        dist = single_device()
    else:
        dist = make_dist(mesh, "train", gb, moe_arch=cfg.uses_moe,
                         topology=topo)
        print(f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))} "
              f"topology {topo.num_nodes}x{topo.devices_per_node} "
              f"bw_ratio={topo.bw_ratio:.1f} "
              f"comm_mode={knobs['comm_mode']} "
              f"exec_mode={knobs['exec_mode']} "
              f"plan_objective={knobs['plan_objective']} "
              f"plan_reuse={args.plan_reuse}")

    luffy = LuffyConfig(
        enable_condensation=not args.no_condensation and cfg.uses_moe,
        enable_migration=not args.no_migration and cfg.uses_moe,
        condense_group=min(128, args.seq_len),
        combine_slack=2.0,
        comm_mode=knobs["comm_mode"],
        exec_mode=knobs["exec_mode"],
        pipeline_chunks=knobs["pipeline_chunks"],
        plan_objective=knobs["plan_objective"],
        plan_reuse=args.plan_reuse,
        similarity_backend=knobs["similarity_backend"],
        lsh_bits=knobs["lsh_bits"],
        condense_reuse=args.condense_reuse,
        condense_reuse_max_age=args.condense_max_age,
        hier_dedup=knobs["hier_dedup"],
        wire_dtype=knobs["wire_dtype"],
        wire_error_feedback=args.wire_error_feedback)
    if calib is not None:
        luffy = calib.apply(luffy)
    ocfg = OptimConfig(name=args.optimizer, lr=args.lr,
                       total_steps=args.steps,
                       warmup_steps=max(2, args.steps // 20))

    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pspecs = model.param_pspecs(dist)
    if dist.enabled:
        params = jax.device_put(
            params, jax.tree.map(lambda s: dist.sharding(s), pspecs))
    expert_shard = None
    if cfg.uses_moe:
        w_up = next(leaf for path, leaf in
                    jax.tree_util.tree_leaves_with_path(params)
                    if "['experts']['w_up']" in jax.tree_util.keystr(path))
        expert_shard = list(w_up.addressable_shards[0].data.shape)
        print(f"experts w_up {list(w_up.shape)}: per-device shard "
              f"{expert_shard}")
    opt_state = optim.init_opt_state(params, ocfg)
    # cross-step wire error feedback (DESIGN.md §15): allocate the
    # residual buffer only when a lossy wire can produce one
    from repro.models import transformer as tf_mod
    use_ef = (luffy.wire_error_feedback and luffy.wire_dtype != "f32"
              and cfg.uses_moe)
    lstate = train_lib.init_luffy_state(
        tf_mod.wire_ef_shape(cfg, gb, args.seq_len) if use_ef else None)
    data = SyntheticLM(cfg, shape)

    # one executable per condensation rate bucket, compiled ahead of
    # its first step so that compile time stays out of the step times
    steps_by_bucket = {}
    compile_s = []

    def get_step(bucket: int, batch):
        if bucket not in steps_by_bucket:
            cap = (train_lib.capacity_for_bucket(cfg, shape, dist, luffy,
                                                 bucket)
                   if cfg.uses_moe else 8)
            fn = train_lib.make_train_step(cfg, luffy, ocfg, dist, cap,
                                           param_pspecs=pspecs)
            t0 = time.perf_counter()
            # params and optimizer state are rebound to the step's
            # outputs, so their input buffers are donated
            steps_by_bucket[bucket] = jax.jit(fn, donate_argnums=(0, 1)) \
                .lower(params, opt_state, lstate, batch).compile()
            compile_s.append(time.perf_counter() - t0)
            ma = steps_by_bucket[bucket].memory_analysis()
            mem = ("" if ma is None else
                   f" memory: argument={ma.argument_size_in_bytes}B "
                   f"output={ma.output_size_in_bytes}B "
                   f"temp={ma.temp_size_in_bytes}B "
                   f"alias={ma.alias_size_in_bytes}B")
            print(f"compile bucket={bucket} {compile_s[-1]}s{mem}",
                  flush=True)
        return steps_by_bucket[bucket]

    # step tracing (DESIGN.md §11): fenced host spans around the loop's
    # upload, step, metrics pull and bucket choice, plus one eager
    # probe_exchange at the end for the plan_build/dispatch/expert_ffn/
    # combine breakdown. Inside the jitted step the phases are named
    # scopes, so the JAX profiler's trace, written beside the Chrome
    # JSON, names each device op's layer, with the host spans on its
    # clock.
    from repro.obs import metrics as obs_metrics
    from repro.obs import trace as obs_trace
    trace_out = args.trace_out or ("trace.json" if args.trace else "")
    tracer = None
    if trace_out:
        tracer = obs_trace.Tracer(fence=True)
        obs_trace.activate(tracer)
        # a cache key without metadata could load an executable compiled
        # from the same code under other scopes, whose op_names are stale
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
        profile_dir = Path(trace_out).with_suffix(".profile")
        jax.profiler.start_trace(str(profile_dir))
    registry = obs_metrics.MetricsRegistry(
        luffy=luffy, run_info={"arch": args.arch, "steps": args.steps,
                               "comm_mode": luffy.comm_mode,
                               "exec_mode": luffy.exec_mode,
                               "calibrated": calib is not None,
                               "autotuned": tuned is not None})

    # residual stream (DESIGN.md §12): the expected step time under the
    # current calibration is anchored on a short measured warmup (the
    # modeled exchange is only part of a full fwd+bwd+opt step); the
    # EWMA detector then flags sustained departures from it
    from repro.obs import monitor as obs_monitor
    monitor = obs_monitor.ResidualMonitor(tolerance=args.drift_tolerance,
                                          k=args.drift_k)
    warmup_ms = []
    expected_step_ms = None
    recalibrated = False

    bucket = 0
    log = []
    step_s = []
    t_start = time.time()
    observed_rate = 0.0
    for i in range(args.steps):
        with obs_trace.phase("upload", cat="step"):
            batch = {k: jnp.asarray(v) for k, v in data.batch(i).items()}
        step_fn = get_step(bucket, batch)
        t0 = time.perf_counter()
        with obs_trace.phase("step", cat="step", step=i):
            params, opt_state, lstate, m = jax.block_until_ready(
                step_fn(params, opt_state, lstate, batch))
        dt = time.perf_counter() - t0
        step_s.append(dt)
        with obs_trace.phase("metrics", cat="step"):
            m = train_lib.finalize_metrics(m, luffy)
        with obs_trace.phase("bucket", cat="step"):
            observed_rate = 0.8 * observed_rate + 0.2 * m["condense_rate"]
            if cfg.uses_moe and luffy.enable_condensation and i >= 3:
                bucket = train_lib.pick_bucket_host(luffy, 0.0,
                                                    observed_rate)
        extra = {}
        step_ms = dt * 1e3
        if expected_step_ms is None:
            if i >= 1:                 # step 0 warms the caches
                warmup_ms.append(step_ms)
            if len(warmup_ms) >= 3:
                expected_step_ms = sum(warmup_ms) / len(warmup_ms)
                if tuned is not None and args.autotune_refine > 0 \
                        and not tuned.refined:
                    # online refinement: re-rank the top candidates
                    # under the measured/modeled step-time ratio
                    ratio = expected_step_ms / max(
                        tuned.modeled_step_ms, 1e-9)
                    refined = obs_at.rerank(
                        tuned, {"step": ratio}, topo=at_topo,
                        chunk_overhead_ms=luffy.chunk_overhead_ms)
                    changed = {k: v for k, v in refined.knobs.items()
                               if k not in explicit
                               and v != tuned.knobs.get(k)}
                    tuned = refined
                    if changed:
                        luffy = dataclasses.replace(luffy, **changed)
                        registry.luffy = luffy
                        steps_by_bucket.clear()
                        expected_step_ms = None
                        warmup_ms.clear()
                        print(f"autotune refine @ step {i}: {changed} "
                              f"(ratio {ratio:.2f})")
        else:
            extra.update(monitor.observe(
                i, {"step": expected_step_ms}, {"step": step_ms}))
            if args.recalibrate_on_drift and args.calibrate \
                    and monitor.drifted and not recalibrated:
                recalibrated = True
                from repro.obs import calibrate as obs_cal
                print(f"drift @ step {i} "
                      f"(phases {monitor.drifted_phases()}): "
                      f"recalibrating", flush=True)
                calib = obs_cal.run_calibration(
                    mesh, topo, out_dir=args.calibrate, force=True)
                luffy = calib.apply(luffy)
                steps_by_bucket.clear()
                monitor.reset()
                expected_step_ms = None
                warmup_ms.clear()
        rec = registry.observe(i, m, time_s=dt, bucket=bucket, **extra)
        log.append(rec)
        if args.metrics_json:
            obs_metrics.write_jsonl(args.metrics_json, rec)
        if i % max(1, args.steps // 20) == 0 or i == args.steps - 1:
            inter = ""
            if (m.get("inter_bytes_flat") or 0.0) > 0:
                inter = (f" inter={m['inter_bytes_dedup']:.0f}B"
                         f"/{m['inter_bytes_flat']:.0f}B")
            print(f"step {i:5d} loss={m['loss']:.4f} "
                  f"cond={m['condense_rate']:.2f} bucket={bucket} "
                  f"local={m['local_frac']:.2f} "
                  f"drop=({m['dispatch_drop']:.3f},{m['combine_drop']:.3f})"
                  f"{inter} {dt * 1e3:.3f}ms", flush=True)
        if args.ckpt and args.ckpt_every and (i + 1) % args.ckpt_every == 0:
            checkpoint.save(args.ckpt, params, pspecs=pspecs, step=i + 1)
    print(f"done: {args.steps} steps in {time.time()-t_start:.1f}s; "
          f"final loss {log[-1]['metrics']['train/loss']:.4f}")
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    if peak is not None:
        print(f"peak_bytes_in_use {peak}")
    if args.ckpt:
        checkpoint.save(args.ckpt, params, pspecs=pspecs, step=args.steps)
    if args.log_file:
        Path(args.log_file).write_text(json.dumps(log, indent=1))
    if tracer is not None:
        if cfg.uses_moe:
            from repro.obs.calibrate import probe_exchange_per_device
            S = min(args.seq_len, 64)
            with obs_trace.phase("probe", cat="probe"):
                per_dev = probe_exchange_per_device(cfg, luffy,
                                                    seq_len=S)
            # probe residuals: join the phases the cost model prices
            # against the fenced probe spans (expert_ffn is the only
            # phase the single-device probe predicts meaningfully —
            # its residual is a direct ffn_speed-staleness check)
            rows = S * cfg.moe.top_k
            pred = {"expert_ffn": rows * 4.0 * cfg.d_model
                    * cfg.moe.d_ff / luffy.gpu_speed * 1e3}
            meas = obs_monitor.measured_phase_ms(tracer)
            res = obs_monitor.ResidualMonitor().observe(
                args.steps, pred, meas, per_device_ms=per_dev)
            rec = registry.observe(args.steps, {}, **res)
            if args.metrics_json:
                obs_metrics.write_jsonl(args.metrics_json, rec)
            disp = res.get("residual_device_dispersion", 1.0)
            print(f"probe: {len(per_dev)} devices, "
                  f"dispersion {disp:.2f}x")
        obs_trace.deactivate()
        jax.profiler.stop_trace()
        tracer.write(trace_out)
        summary = tracer.summary()
        steps = summary.get("step", {})
        print(f"trace: {len(tracer.events)} events -> {trace_out} "
              f"(step total {steps.get('total_us', 0.0)/1e3:.1f}ms over "
              f"{steps.get('count', 0)} spans); profiler trace -> "
              f"{profile_dir}")
    return {"device": device, "layers": cfg.num_layers,
            "expert_shard": expert_shard,
            "losses": [r["metrics"]["train/loss"] for r in log],
            "step_s": step_s, "compile_s": compile_s,
            "peak_bytes_in_use": peak}


if __name__ == "__main__":
    main()
