"""Serving driver: fixed batches or continuous batching (DESIGN.md §13).

    PYTHONPATH=src python -m repro.launch.serve --arch olmoe-1b-7b \
        --reduced --batch 4 --prompt-len 32 --gen 16

The MoE sublayers run through the same ``repro.plan`` build/execute core
as training (DESIGN.md §7), so the execution-schedule knobs apply here
too: ``--exec-mode pipeline`` chunks the prefill dispatch capacity and
overlaps the expert collectives with compute, ``--exec-mode
decode_overlap`` issues the decode combine psum concurrently with the
shared-expert FFN, ``--prefill batch`` runs one whole-prompt
``serve.prefill`` pass through that executor (and times it) before the
cache-building decode loop.

``--continuous`` switches the unit of work from a step to a *request*
(repro.serve.scheduler): a synthetic bursty-arrivals workload is
admitted into free cache slots between decode steps, finished sequences
are evicted so their slots recycle mid-stream, and per-request SLOs
(queue/TTFT/per-token latency) flow through the ``repro.obs`` metrics
registry (``--metrics-json``). With ``--plan-cache --precompute-plans``
the decode template is warmed ahead of time, so the steady-state loop
makes zero ``build_exchange_plan`` calls.
"""
from __future__ import annotations

import argparse
import time


def _serve_continuous(args, cfg, luffy, dist, params, plan_cache,
                      registry):
    """The continuous-batching request loop (one decode step per
    iteration; admissions and evictions happen between steps)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.obs import trace as obs_trace
    from repro.serve import engine
    from repro.serve.scheduler import ContinuousScheduler

    B, S = args.batch, args.prompt_len
    # per-slot relative frames: one occupant never holds more than
    # prompt + gen positions, no matter how long the run is
    s_max = S + args.gen
    r = np.random.default_rng(0)
    prompts = r.integers(1, cfg.vocab_size,
                         (args.requests, S)).astype(np.int32)
    # synthetic bursty arrivals: bursts of --burst requests land
    # together every --arrival-every decode steps
    arrival_step = [(i // max(1, args.burst)) * max(1, args.arrival_every)
                    for i in range(args.requests)]

    if plan_cache is not None and args.precompute_plans and cfg.uses_moe:
        from repro.plan.cache import precompute_decode_plans
        key = precompute_decode_plans(cfg, luffy, dist, B, plan_cache)
        print(f"precomputed decode plan: {key}")

    cache = engine.cache_struct(cfg, B, s_max, as_struct=False)
    dec = jax.jit(lambda p, c, t: engine.decode_step(
        p, cfg, luffy, dist, c, t, plan_cache=plan_cache))
    sched = ContinuousScheduler(B)
    step = 0
    submitted = 0
    t0 = time.time()
    while step < args.max_steps:
        now = time.time()
        while submitted < args.requests \
                and arrival_step[submitted] <= step:
            sched.submit(prompts[submitted], args.gen, now=now)
            submitted += 1
        if sched.all_done():
            if submitted >= args.requests:
                break
            step += 1          # idle until the next burst lands
            continue
        for slot, _req in sched.admit(now=now):
            cache = engine.admit_slot(cache, slot, int(cache["pos"]))
        toks = sched.next_feed()
        with obs_trace.phase("decode", cat="step", step=step,
                             active=sched.active_slots) as _sp:
            logits, cache = dec(params, cache, jnp.asarray(toks))
            logits = _sp.fence(logits)
        sched.observe(np.asarray(logits), now=time.time())
        if registry is not None:
            from repro.obs.metrics import write_jsonl
            write_jsonl(args.metrics_json,
                        registry.observe(step, sched.step_metrics()))
        step += 1
    dt = time.time() - t0
    done = sched.done
    tok = sched.generated_tokens
    print(f"continuous: {len(done)}/{args.requests} requests, "
          f"{tok} tokens in {dt:.2f}s ({tok / max(dt, 1e-9):.1f} tok/s), "
          f"{step} steps, slot_churn={sched.slot_churn}")
    if done:
        def _mean(name):
            vals = [getattr(q, name) for q in done]
            vals = [v for v in vals if v is not None]
            return float(np.mean(vals)) if vals else float("nan")
        print(f"SLO: queue {_mean('queue_ms'):.1f}ms "
              f"ttft {_mean('ttft_ms'):.1f}ms "
              f"tpot {_mean('tpot_ms'):.1f}ms")
    if plan_cache is not None:
        print(f"plan cache: {plan_cache.stats()}")
    if sched.queue or sched.active_slots:
        print(f"WARNING: --max-steps hit with {len(sched.queue)} queued "
              f"and {sched.active_slots} active requests")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="moe-gpt2")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--model-axis", type=int, default=4)
    ap.add_argument("--prefill", choices=["step", "batch"], default="step",
                    help="step: feed the prompt token-by-token (cache-"
                         "correct for every arch family); batch: also run "
                         "one whole-prompt prefill through the shared "
                         "build/execute MoE core (times the pipelined "
                         "serving forward)")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching (repro.serve.scheduler, "
                         "DESIGN.md §13): a synthetic bursty-arrivals "
                         "workload of --requests prompts is admitted "
                         "into free cache slots between decode steps "
                         "and evicted on finish — slot reuse instead of "
                         "fixed batches; per-request SLOs go to "
                         "--metrics-json")
    ap.add_argument("--requests", type=int, default=8,
                    help="total synthetic requests for --continuous")
    ap.add_argument("--burst", type=int, default=3,
                    help="requests arriving together per burst "
                         "(--continuous)")
    ap.add_argument("--arrival-every", type=int, default=4,
                    help="decode steps between bursts (--continuous)")
    ap.add_argument("--max-steps", type=int, default=512,
                    help="hard step budget for --continuous (guards "
                         "against an undrainable queue)")
    ap.add_argument("--exec-mode",
                    choices=["sync", "pipeline", "decode_overlap"],
                    default=None,
                    help="MoE execution schedule: strict order, chunked "
                         "software pipeline with compute/comm overlap "
                         "on prefill (bit-identical; DESIGN.md §6), or "
                         "the decode combine psum issued concurrently "
                         "with the shared-expert FFN (bit-identical; "
                         "DESIGN.md §13; default sync)")
    ap.add_argument("--pipeline-chunks", type=int, default=None,
                    help="capacity chunks for --exec-mode pipeline "
                         "(default 4; under --plan-objective overlap "
                         "the estimate search picks the count)")
    ap.add_argument("--plan-cache", default="",
                    help="directory for the serialized ExchangePlan "
                         "cache (DESIGN.md §9): prefill AND decode look "
                         "up precomputed static plans by batch-shape "
                         "key and execute them without planning")
    ap.add_argument("--precompute-plans", action="store_true",
                    help="warm --plan-cache with this run's prefill "
                         "and decode shapes before serving "
                         "(ahead-of-time planning)")
    ap.add_argument("--hier-dedup", default=None, choices=["off", "on"],
                    help="deduplicated hier wire format on the batched "
                         "prefill exchange (repro.condense.wire, "
                         "DESIGN.md §10): each prompt token's payload "
                         "crosses the inter-node links once per (token, "
                         "node) — serving never condenses, but the "
                         "top-k copy dedup still applies. Needs a "
                         "hierarchical mesh; the flat host mesh keeps "
                         "the dense wire")
    ap.add_argument("--plan-objective", default=None,
                    choices=["traffic", "overlap"],
                    help="migration planner objective (DESIGN.md §7; "
                         "default traffic). RESERVED for a future "
                         "serving migration mode: today serving forces "
                         "migration off (prompts are never re-homed), "
                         "so both choices build identical vanilla plans "
                         "— the flag only threads the config through "
                         "for parity with train/dryrun")
    ap.add_argument("--similarity-backend", default=None,
                    choices=["exact", "lsh"],
                    help="condensation similarity backend (DESIGN.md "
                         "§10; default exact). Serving never condenses "
                         "— the flag threads the config through for "
                         "parity with train/dryrun and, with the "
                         "PR-7 precedence, overrides a TunedConfig's "
                         "backend choice explicitly")
    ap.add_argument("--lsh-bits", type=int, default=None,
                    help="signed random projections per LSH bucket code "
                         "(default 8; parity flag, see "
                         "--similarity-backend)")
    ap.add_argument("--wire-dtype", default=None,
                    choices=["f32", "bf16", "f8e4m3"],
                    help="precision activation rows ship at on node-"
                         "crossing exchange hops (DESIGN.md §14): "
                         "identity wire, bf16 cast, or f8e4m3 with "
                         "per-32-element f32 scales; part of the plan "
                         "cache key (default f32)")
    ap.add_argument("--condense-reuse", default="off",
                    choices=["off", "signature", "always"],
                    help="cross-layer condense-plan reuse (DESIGN.md "
                         "§10; parity flag — serving forces "
                         "condensation off, so this only threads the "
                         "config through like train/dryrun)")
    ap.add_argument("--autotune", default="",
                    help="TunedConfig artifact dir (repro.obs.autotune): "
                         "fill the execution knobs the CLI left unset "
                         "from the tuned artifact for this mesh's "
                         "topology (explicit flags always override; "
                         "DESIGN.md §12)")
    ap.add_argument("--autotune-force", action="store_true",
                    help="re-run the autotune search even when a valid "
                         "artifact exists")
    ap.add_argument("--metrics-json", default="",
                    help="append unified metrics records (repro.obs."
                         "metrics JSONL): one batched-prefill row plus "
                         "one row per decode step (serve/* SLO and "
                         "occupancy keys under --continuous)")
    ap.add_argument("--trace", action="store_true",
                    help="step tracing (repro.obs.trace): fenced spans "
                         "around batched prefill, the step-wise prompt "
                         "feed and every decode step; writes "
                         "Chrome-trace JSON (see --trace-out)")
    ap.add_argument("--trace-out", default="",
                    help="trace JSON path (implies --trace; default "
                         "trace.json)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import serve_lib
    from repro.config import LuffyConfig, reduced
    from repro.configs import get_config
    from repro.dist import DistContext, make_dist, single_device
    from repro.launch.mesh import make_host_mesh
    from repro.models.model import build_model
    from repro.launch.device import device_banner, enable_compile_cache
    device_banner()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    if len(jax.devices()) > 1:
        mesh = make_host_mesh(model=args.model_axis)
        dist = make_dist(mesh, "decode", args.batch, moe_arch=cfg.uses_moe)
    else:
        dist = single_device()
    # knob resolution (DESIGN.md §12): explicit flags > tuned artifact
    # (--autotune) > defaults. Serving never migrates or condenses, so
    # the execution knobs plus the similarity pair (parity with train —
    # an explicit --similarity-backend beats the artifact's choice) are
    # taken from the artifact.
    from repro.config import resolve_pipeline_chunks
    from repro.obs import autotune as obs_at
    serve_knobs = ("exec_mode", "pipeline_chunks", "plan_objective",
                   "hier_dedup", "similarity_backend", "lsh_bits",
                   "wire_dtype")
    explicit = {k for k in serve_knobs
                if getattr(args, k) is not None}
    tuned = None
    if args.autotune and cfg.uses_moe:
        from repro.comm.topology import Topology
        at_topo = (Topology.from_mesh(mesh) if len(jax.devices()) > 1
                   else Topology.flat(1))
        tuned = obs_at.run_autotune(
            topo=at_topo, out_dir=args.autotune,
            force=args.autotune_force,
            tokens=args.batch * args.prompt_len,
            top_k=cfg.moe.top_k, d_model=cfg.d_model,
            d_ff=cfg.moe.d_ff, num_layers=cfg.num_layers,
            n_slots=args.batch, num_experts=cfg.moe.num_experts,
            group_size=min(128, args.prompt_len),
            # decode workload term (DESIGN.md §13): lets the grid see
            # what decode_overlap buys on this arch and fabric
            decode_tokens=args.batch,
            d_ff_shared=cfg.moe.d_ff * cfg.moe.num_shared_experts)
        print(f"autotune {tuned.key}: {tuned.knobs} modeled "
              f"{tuned.modeled_step_ms:.3f}ms vs default "
              f"{tuned.default_step_ms:.3f}ms")
    knobs = dict(obs_at.DEFAULT_KNOBS)
    knobs["pipeline_chunks"] = None    # sentinel: resolve by objective
    if tuned is not None:
        knobs.update({k: v for k, v in tuned.knobs.items()
                      if k in serve_knobs and k not in explicit})
    for k in explicit:
        knobs[k] = getattr(args, k)
    if "hier_dedup" not in explicit and knobs["hier_dedup"] == "on":
        knobs["hier_dedup"] = "off"   # serving runs comm_mode="flat";
                                      # the dedup wire needs hier comm
    if knobs["pipeline_chunks"] is None:
        knobs["pipeline_chunks"] = resolve_pipeline_chunks(
            None, knobs["plan_objective"])
    pipeline_chunks = knobs["pipeline_chunks"]
    luffy = LuffyConfig(enable_condensation=False, enable_migration=False,
                        exec_mode=knobs["exec_mode"],
                        pipeline_chunks=pipeline_chunks,
                        plan_objective=knobs["plan_objective"],
                        similarity_backend=knobs["similarity_backend"],
                        lsh_bits=knobs["lsh_bits"],
                        condense_reuse=args.condense_reuse,
                        hier_dedup=knobs["hier_dedup"],
                        wire_dtype=knobs["wire_dtype"])
    print(f"exec_mode={luffy.exec_mode} chunks={pipeline_chunks} "
          f"plan_objective={luffy.plan_objective} "
          f"similarity_backend={luffy.similarity_backend} "
          f"plan_cache={args.plan_cache or 'off'}")

    from repro.obs import trace as obs_trace
    trace_out = args.trace_out or ("trace.json" if args.trace else "")
    tracer = None
    if trace_out:
        tracer = obs_trace.Tracer(fence=True)
        obs_trace.activate(tracer)
    registry = None
    if args.metrics_json:
        from repro.obs.metrics import MetricsRegistry
        registry = MetricsRegistry(luffy=luffy, run_info={
            "launcher": "serve", "arch": args.arch,
            "continuous": bool(args.continuous), "batch": args.batch,
            "prompt_len": args.prompt_len, "gen": args.gen})

    plan_cache = None
    if args.plan_cache:
        from repro.plan.cache import PlanCache
        plan_cache = PlanCache(args.plan_cache)

    if args.continuous:
        _serve_continuous(args, cfg, luffy, dist, params, plan_cache,
                          registry)
        if tracer is not None:
            obs_trace.deactivate()
            tracer.write(trace_out)
            print(f"trace: {len(tracer.events)} events -> {trace_out}")
        return

    r = np.random.default_rng(0)
    B, S = args.batch, args.prompt_len
    prompts = jnp.asarray(r.integers(1, cfg.vocab_size, (B, S)), jnp.int32)
    s_max = S + args.gen
    if plan_cache is not None and args.prefill != "batch":
        print("NOTE: --plan-cache on the fixed-batch driver engages the "
              "batched prefill (--prefill batch) and the decode "
              "template; the step-wise prompt feed reuses the decode "
              "template too")
    if args.prefill == "batch":
        # whole-prompt forward through the shared build/execute MoE core
        # (the pipelined serving path inherited from repro.plan)
        if len(jax.devices()) > 1:
            pdist = make_dist(mesh, "prefill", B, moe_arch=cfg.uses_moe)
        else:
            pdist = single_device()
        if plan_cache is not None and args.precompute_plans \
                and cfg.uses_moe:
            import dataclasses as _dc
            from repro.plan.cache import precompute_prefill_plans
            nl = _dc.replace(luffy, enable_condensation=False,
                             enable_migration=False)
            key = precompute_prefill_plans(cfg, nl, pdist, B, S,
                                           plan_cache)
            print(f"precomputed prefill plan: {key}")
        pf = jax.jit(lambda p, t: model.prefill(
            p, t, s_max, luffy=luffy, dist=pdist,
            plan_cache=plan_cache)[0])
        logits_pf = pf(params, prompts)
        jax.block_until_ready(logits_pf)
        t0 = time.time()
        with obs_trace.phase("prefill_batch", cat="step") as _sp:
            logits_pf = jax.block_until_ready(pf(params, prompts))
        dt = time.time() - t0
        print(f"batched prefill({B}x{S} tokens): {dt:.3f}s "
              f"({B * S / max(dt, 1e-9):.0f} tok/s)")
        if registry is not None:
            from repro.obs.metrics import write_jsonl
            write_jsonl(args.metrics_json, registry.observe(
                0, {"time_s": dt}, phase="prefill_batch",
                prefill_tokens=B * S))
        if plan_cache is not None:
            print(f"plan cache: {plan_cache.stats()}")
    if plan_cache is not None and args.precompute_plans and cfg.uses_moe:
        from repro.plan.cache import precompute_decode_plans
        key = precompute_decode_plans(cfg, luffy, dist, B, plan_cache)
        print(f"precomputed decode plan: {key}")
    t0 = time.time()
    cache = serve_lib.cache_struct(cfg, B, s_max, as_struct=False)
    dec = jax.jit(lambda p, c, t: serve_lib.decode_step(
        p, cfg, luffy, dist, c, t, plan_cache=plan_cache))
    # feed the prompt token by token (cache-correct for every arch family)
    logits = None
    with obs_trace.phase("prefill_step", cat="step", tokens=S) as _sp:
        for t in range(S):
            logits, cache = dec(params, cache, prompts[:, t:t + 1])
        logits = _sp.fence(logits)
    print(f"prefill({S} tokens): {time.time()-t0:.2f}s")
    out = []
    t0 = time.time()
    for i in range(args.gen):
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        out.append(np.asarray(nxt[:, 0]))
        ts = time.time()
        with obs_trace.phase("decode", cat="step", step=i) as _sp:
            logits, cache = dec(params, cache, nxt)
            logits = _sp.fence(logits)
        if registry is not None:
            from repro.obs.metrics import write_jsonl
            write_jsonl(args.metrics_json, registry.observe(
                i + 1, {"time_s": time.time() - ts,
                        "generated_tokens": B}))
    dt = time.time() - t0
    toks = int(np.asarray(out).size)
    print(f"decode: {toks} tokens in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s batch={B})")
    print("sample token ids:", [int(x) for x in np.asarray(out)[:, 0][:10]])
    if plan_cache is not None:
        print(f"plan cache: {plan_cache.stats()}")
    if tracer is not None:
        obs_trace.deactivate()
        tracer.write(trace_out)
        print(f"trace: {len(tracer.events)} events -> {trace_out}")


if __name__ == "__main__":
    main()
