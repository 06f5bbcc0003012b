"""repro.plan: ExchangePlan build/execute equivalence with moe_core, the
planner-objective registry ("traffic" == legacy exactly, "overlap" never
worse in modeled exposed time), the shared phase-estimate model, and the
8-device golden grid {vanilla, migrate} × {condense} × {flat, hier} ×
{sync, pipeline} plus the pipelined serving prefill (DESIGN.md §7)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import CommContext, Topology
from repro.config import LuffyConfig, ModelConfig, MoEConfig
from repro.core import moe_layer as ml
from repro.plan import (ObjectiveContext, PlanEstimate,
                        available_objectives, build_exchange_plan,
                        estimate_exchange, execute_plan, get_objective,
                        plan_migration_with_objective, register_objective)
from repro.plan import objectives as obj

ROOT = os.path.join(os.path.dirname(__file__), "..")


# ---------------------------------------------------------------------------
# objective registry
# ---------------------------------------------------------------------------

def test_registry_lookup_and_error():
    assert set(available_objectives()) >= {"traffic", "overlap"}
    assert get_objective("traffic") is obj.traffic_objective
    with pytest.raises(ValueError, match="traffic"):
        get_objective("nope")


def test_registry_extensible():
    @register_objective("_test_identity")
    def identity_objective(counts, seq_lens, n_per_dev, *, ctx, q=3,
                           d_model=1024, speed=1e13):
        from repro.core.migration import identity_plan
        return identity_plan(counts.shape[0], n_per_dev)

    try:
        assert "_test_identity" in available_objectives()
        plan = plan_migration_with_objective(
            np.ones((4, 2)), np.arange(4.0), 2, objective="_test_identity")
        np.testing.assert_array_equal(np.asarray(plan.perm), np.arange(4))
    finally:
        obj.OBJECTIVES.pop("_test_identity")


def _instance(seed, n_slots, M):
    r = np.random.default_rng(seed)
    counts = (r.random((n_slots, M)) ** 3)
    counts = (counts / counts.sum(1, keepdims=True) * 100)
    counts = counts + r.random(counts.shape) * 1e-3   # break ties
    lens = r.integers(10, 100, n_slots).astype(np.float64)
    return counts.astype(np.float64), lens


def test_traffic_objective_reproduces_legacy_plans():
    """"traffic" through the registry == the pre-registry planner calls,
    both with and without a hierarchical topology."""
    from repro.core import migration as mig
    topo = Topology(2, 2)
    counts, lens = _instance(0, 8, 4)
    for ctx, link_cost in ((ObjectiveContext(topo=topo), topo.link_cost()),
                           (ObjectiveContext(topo=None), None),
                           (ObjectiveContext(topo=Topology.flat(4)), None)):
        got = plan_migration_with_objective(counts, lens, 2,
                                            objective="traffic", ctx=ctx,
                                            q=2)
        want = mig.plan_migration_np(counts, lens, 2, q=2,
                                     link_cost=link_cost)
        np.testing.assert_array_equal(np.asarray(got.assign),
                                      np.asarray(want.assign))
        assert float(got.traffic_after) == float(want.traffic_after)


# ---------------------------------------------------------------------------
# "overlap" objective: exposed-time model + never-worse guarantee
# ---------------------------------------------------------------------------

def _inter_bound_ctx(topo, chunks=4):
    """A pipeline where the inter-node phase is the bottleneck stage —
    the regime the overlap objective exists for."""
    return ObjectiveContext(topo=topo, ffn_ms=5.0, dispatch_intra_ms=1.0,
                            dispatch_inter_ms=8.0, chunks=chunks,
                            row_bytes=4096.0)


def test_exposed_link_cost_amplifies_inter_node_cost():
    topo = Topology(2, 4)                       # bw_ratio 4
    ctx = _inter_bound_ctx(topo, chunks=4)
    cost = obj.exposed_link_cost(ctx)
    assert cost[0, 1] == 1.0                    # intra normalized
    # hidden intra (1/n) vs exposed inter (1) -> n * bw_ratio
    assert cost[0, 4] == pytest.approx(4 * topo.bw_ratio)
    # sync (1 chunk) degenerates to the plain link-cost matrix
    sync = obj.exposed_link_cost(
        ObjectiveContext(topo=topo, ffn_ms=5.0, dispatch_intra_ms=1.0,
                         dispatch_inter_ms=8.0, chunks=1))
    np.testing.assert_allclose(sync, topo.link_cost())


def test_overlap_objective_never_worse_2x4():
    """Satellite acceptance: on a 2×4 hier topology the "overlap" plan's
    modeled exposed time is never worse than the "traffic" plan's, and
    the portfolio actually wins on some instances."""
    topo = Topology(2, 4)
    ctx = _inter_bound_ctx(topo)
    M, n_per = topo.num_devices, 2
    strictly_better = 0
    for seed in range(40):
        counts, lens = _instance(seed, M * n_per, M)
        p_t = plan_migration_with_objective(counts, lens, n_per,
                                            objective="traffic", ctx=ctx)
        p_o = plan_migration_with_objective(counts, lens, n_per,
                                            objective="overlap", ctx=ctx)
        t_t = float(obj.plan_exposed_ms(counts, np.asarray(p_t.assign),
                                        ctx))
        t_o = float(obj.plan_exposed_ms(counts, np.asarray(p_o.assign),
                                        ctx))
        assert t_o <= t_t + 1e-9, (seed, t_o, t_t)
        # the overlap plan is still a valid capacity-respecting bijection
        perm = np.asarray(p_o.perm)
        assert sorted(perm.tolist()) == list(range(M * n_per))
        assert (np.bincount(np.asarray(p_o.assign), minlength=M)
                == n_per).all()
        if t_o < t_t - 1e-9:
            strictly_better += 1
    assert strictly_better >= 1


def test_overlap_objective_traced_matches_host():
    """jax backend (inside jit) == numpy backend for both objectives."""
    topo = Topology(2, 4)
    ctx = _inter_bound_ctx(topo)
    for seed in (3, 7):
        counts, lens = _instance(seed, 16, 8)

        @jax.jit
        def go(c, l):
            p = plan_migration_with_objective(c, l, 2, objective="overlap",
                                              ctx=ctx)
            return p.assign, p.perm

        a, perm = go(jnp.asarray(counts, jnp.float32),
                     jnp.asarray(lens, jnp.float32))
        p_np = plan_migration_with_objective(counts, lens, 2,
                                             objective="overlap", ctx=ctx)
        np.testing.assert_array_equal(np.asarray(a),
                                      np.asarray(p_np.assign))
        np.testing.assert_array_equal(np.asarray(perm),
                                      np.asarray(p_np.perm))


def test_overlap_degenerates_without_hierarchy_or_pipeline():
    """Flat fabric or sync execution: nothing to hide, so "overlap"
    returns the traffic plan exactly."""
    counts, lens = _instance(1, 8, 4)
    flat_ctx = ObjectiveContext(topo=Topology.flat(4), chunks=8)
    sync_ctx = _inter_bound_ctx(Topology(2, 2), chunks=1)
    for ctx in (flat_ctx, sync_ctx):
        p_t = plan_migration_with_objective(counts, lens, 2,
                                            objective="traffic", ctx=ctx)
        p_o = plan_migration_with_objective(counts, lens, 2,
                                            objective="overlap", ctx=ctx)
        np.testing.assert_array_equal(np.asarray(p_t.assign),
                                      np.asarray(p_o.assign))


# ---------------------------------------------------------------------------
# phase estimates
# ---------------------------------------------------------------------------

def test_estimate_exchange_contracts():
    topo = Topology(2, 4)
    est = estimate_exchange(4096, 2, 64, topo=topo, r_cond=0.25,
                            locality=0.4, ffn_ms=3.0, chunks=4)
    assert isinstance(est, PlanEstimate)
    assert est.chunks == 4
    assert est.overlap_ms <= est.sync_ms
    assert est.inter_dispatch_bytes <= est.flat_inter_dispatch_bytes
    assert est.intra_combine_bytes == pytest.approx(
        est.intra_dispatch_bytes * 0.6)
    assert est.inter_combine_bytes == pytest.approx(
        est.inter_dispatch_bytes * 0.6)
    assert est.combine_ms < est.dispatch_ms         # locality gain
    assert est.speedup == pytest.approx(est.sync_ms / est.overlap_ms)
    # planning search picks the best chunk count over 1..16
    opt = estimate_exchange(4096, 2, 64, topo=topo, r_cond=0.25,
                            locality=0.4, ffn_ms=3.0, chunks=None)
    assert opt.overlap_ms <= est.overlap_ms + 1e-12
    # flat fabric: no inter-node bytes, dedup changes nothing
    flat = estimate_exchange(4096, 2, 64, topo=Topology.flat(8),
                             ffn_ms=3.0, chunks=2)
    assert flat.inter_dispatch_bytes == 0.0
    assert flat.intra_dispatch_bytes == flat.flat_intra_dispatch_bytes


# ---------------------------------------------------------------------------
# build/execute == moe_core (single device, eager: bitwise)
# ---------------------------------------------------------------------------

def _mk(num_experts=4, top_k=2, shared=1):
    return ModelConfig(
        name="t", kind="decoder", family="moe", num_layers=2,
        d_model=32, d_ff=64, vocab_size=128,
        moe=MoEConfig(num_experts=num_experts, top_k=top_k, d_ff=64,
                      num_shared_experts=shared),
        layer_ffn_pattern=("moe",), compute_dtype="float32",
        param_dtype="float32")


@pytest.mark.parametrize("condense", [False, True])
def test_build_execute_matches_moe_core_single_device(rng, condense):
    from repro.core.gating import gate_apply
    from repro.models.blocks import _dtype
    cfg = _mk()
    p = ml.moe_init(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(rng.standard_normal((2, 16, cfg.d_model)), jnp.float32)
    sb = {"labels": jnp.zeros((2, 16), jnp.int32),
          "seq_len": jnp.full((2,), 16, jnp.int32)}
    luffy = LuffyConfig(enable_condensation=condense,
                        enable_migration=False, condense_group=16)
    thr = jnp.float32(0.9)
    y1, sb1, s1, aux1 = ml.moe_core(p, x, dict(sb), cfg, luffy,
                                    mode="vanilla", capacity=256,
                                    axis_name=None, threshold=thr,
                                    group_size=16)
    comm = CommContext.local()
    xn = ml._rms(x.reshape(-1, cfg.d_model),
                 p["norm"]["scale"]).astype(_dtype(cfg.compute_dtype))
    gate = gate_apply(p["router"], xn, cfg.moe.top_k)
    plan = build_exchange_plan(gate, xn, cfg, luffy, comm, mode="vanilla",
                               capacity=256, sideband=sb, threshold=thr,
                               group_size=16)
    y2, aux2 = execute_plan(p, x, dict(sb), plan, cfg)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    for a, b in zip(aux1, aux2.moe):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if condense:
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(aux2.s_next))
    # plan shape/static contracts
    assert plan.comm.mode == "local" and plan.comm.size() == 1
    assert plan.chunks.n_chunks == 1 and not plan.pipelined
    assert plan.estimate is None            # no topology to price
    assert plan.objective == "traffic"
    assert plan.expert_idx.shape == (32, cfg.moe.top_k)
    assert plan.condense == condense


def test_comm_context_local_identity():
    c = CommContext.local()
    assert c.size() == 1 and c.index() == 0 and c.axis_name is None
    x = jnp.arange(8.0).reshape(2, 4)
    np.testing.assert_array_equal(np.asarray(c.all_to_all(x)),
                                  np.asarray(x))
    np.testing.assert_array_equal(np.asarray(c.combine(x)), np.asarray(x))
    assert c.link_cost() is None
    # ensure(): the one call-boundary normalization
    assert CommContext.ensure(c, "model") is c
    assert CommContext.ensure(None, None).mode == "local"
    assert CommContext.ensure(None, "model").mode == "flat"


# ---------------------------------------------------------------------------
# 8-device golden grid + serving prefill (subprocesses, like test_comm)
# ---------------------------------------------------------------------------

def _run(script_body: str) -> str:
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses
        import itertools
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.comm import CommContext, Topology, make_mesh
        from repro.configs import get_config
        from repro.config import reduced, LuffyConfig, ShapeConfig
        from repro.models.model import build_model
        from repro.dist import DistContext, make_dist
        from repro.data import SyntheticLM
        from repro.core.moe_layer import capacity_for
    """) + textwrap.dedent(script_body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", script], cwd=ROOT,
                         capture_output=True, text=True, env=env,
                         timeout=1200)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_golden_grid_8dev_bit_identity():
    """Golden equivalence: the build/execute forward is invariant across
    {sync, pipeline} × {flat, hier} for {vanilla, migrate} ×
    {condense on/off} on one hierarchical 8-device mesh — i.e. exactly
    the pre-refactor guarantees, now through the ExchangePlan API. The
    "overlap" objective under sync (1 chunk) must also be bit-identical
    (it degenerates to "traffic"), and under a pipelined executor it must
    still train to a finite loss with a valid slot bijection."""
    out = _run("""
        cfg = reduced(get_config("moe-gpt2"), num_layers=2, d_model=128)
        cfg = dataclasses.replace(cfg, compute_dtype="float32")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        shape = ShapeConfig("t", 64, 8, "train")
        data = SyntheticLM(cfg, shape)
        b = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
        cap = capacity_for(cfg.moe, 64, cfg.moe.num_experts, slack=8.0)
        mesh = make_mesh((2, 2, 2), ("data", "node", "local"))
        dist = DistContext(mesh, batch_axes=("data", "node", "local"),
                           seq_axis=None, fsdp_axes=("data",),
                           model_axis=("node", "local"),
                           topology=Topology(2, 2))

        def loss(luffy):
            l, m = jax.jit(lambda p, bb: model.train_loss(
                p, bb, jnp.float32(0.4), luffy=luffy, dist=dist,
                capacity=cap))(params, b)
            return float(l), {k: float(v) for k, v in m.items()}

        for mig, cond in itertools.product((True, False), repeat=2):
            base = LuffyConfig(enable_condensation=cond,
                               enable_migration=mig, combine_slack=4.0,
                               condense_group=32, comm_mode="flat")
            l0, m0 = loss(base)
            # inter_bytes_dedup is the one metric ALLOWED to differ when
            # comm_mode flips: the flat wire ships every copy, so its
            # ledger reports dedup == flat by design (DESIGN.md §5)
            variants = [
                (dataclasses.replace(base, comm_mode="hier"), True),
                (dataclasses.replace(base, exec_mode="pipeline",
                                     pipeline_chunks=3), False),
                (dataclasses.replace(base, comm_mode="hier",
                                     exec_mode="pipeline",
                                     pipeline_chunks=3), True),
                (dataclasses.replace(base, plan_objective="overlap"),
                 False),
            ]
            for i, (v, hier) in enumerate(variants):
                lv, mv = loss(v)
                assert l0 == lv, (mig, cond, i, l0, lv)
                for k in m0:
                    if hier and k == "inter_bytes_dedup":
                        continue
                    assert m0[k] == mv[k], (mig, cond, i, k)
        # pipelined "overlap" objective: a different (still valid) plan is
        # allowed — require a finite loss and healthy ledger instead
        ov = LuffyConfig(enable_condensation=True, enable_migration=True,
                         combine_slack=4.0, condense_group=32,
                         comm_mode="hier", exec_mode="pipeline",
                         pipeline_chunks=3, plan_objective="overlap")
        lo, mo = loss(ov)
        assert np.isfinite(lo), lo
        assert mo["traffic_after"] <= mo["traffic_before"] + 1e-5
        assert 0.0 <= mo["local_frac"] <= 1.0
        print("OK")
    """)
    assert "OK" in out


def test_build_execute_matches_moe_core_8dev_shardmap():
    """Direct ExchangePlan API == moe_core inside shard_map, on the
    hardest combo (hier comm × pipeline × migrate × condense)."""
    out = _run("""
        from repro.core import moe_layer as ml
        from repro.core.gating import gate_apply
        from repro.plan import build_exchange_plan, execute_plan
        from repro.models.blocks import _dtype

        cfg = dataclasses.replace(
            reduced(get_config("moe-gpt2"), num_layers=2, d_model=64),
            compute_dtype="float32")
        p = ml.moe_init(jax.random.PRNGKey(1), cfg)
        mesh = make_mesh((2, 2, 2), ("data", "node", "local"))
        topo = Topology(2, 2)
        comm = CommContext.build("hier", ("node", "local"), topo)
        luffy = LuffyConfig(enable_condensation=True, enable_migration=True,
                            combine_slack=4.0, condense_group=16,
                            comm_mode="hier", exec_mode="pipeline",
                            pipeline_chunks=3)
        n_seq, S, d = 2, 32, cfg.d_model
        r = np.random.default_rng(0)
        x = jnp.asarray(r.standard_normal((16, S, d)), jnp.float32)
        lbl = jnp.zeros((16, S), jnp.int32)
        slen = jnp.asarray(r.integers(S // 2, S + 1, (16,)), jnp.int32)
        cap = ml.capacity_for(cfg.moe, n_seq * S, cfg.moe.num_experts,
                              slack=4.0)
        thr = jnp.float32(0.5)

        def inner_core(p_l, x_l, lbl_l, sl_l):
            sb = {"labels": lbl_l, "seq_len": sl_l}
            y, sb2, s_next, aux = ml.moe_core(
                p_l, x_l, sb, cfg, luffy, mode="migrate", capacity=cap,
                comm=comm, threshold=thr, group_size=16,
                combine_slack=4.0)
            return y, sb2["labels"], sb2["seq_len"], s_next

        def inner_plan(p_l, x_l, lbl_l, sl_l):
            sb = {"labels": lbl_l, "seq_len": sl_l}
            xn = ml._rms(x_l.reshape(-1, d), p_l["norm"]["scale"]
                         ).astype(_dtype(cfg.compute_dtype))
            gate = gate_apply(p_l["router"], xn, cfg.moe.top_k)
            plan = build_exchange_plan(
                gate, xn, cfg, luffy, comm, mode="migrate", capacity=cap,
                sideband=sb, threshold=thr, group_size=16,
                combine_slack=4.0)
            assert plan.pipelined and plan.chunks.n_chunks == 3
            assert plan.estimate is not None
            assert plan.migrate and plan.condense
            y, aux = execute_plan(p_l, x_l, sb, plan, cfg)
            return y, aux.sideband["labels"], aux.sideband["seq_len"], \\
                aux.s_next

        ba = ("data", "node", "local")
        ma = ("node", "local")
        p_specs = jax.tree.map(lambda _: P(), p)
        p_specs["experts"] = jax.tree.map(lambda _: P(ma, None, None),
                                          p["experts"])
        specs = dict(
            in_specs=(p_specs,
                      P(ba, None, None), P(ba, None), P(ba)),
            out_specs=(P(ba, None, None), P(ba, None), P(ba),
                       P(ba, None, None)))
        f1 = jax.jit(shard_map(inner_core, mesh=mesh, **specs))
        f2 = jax.jit(shard_map(inner_plan, mesh=mesh, **specs))
        o1 = f1(p, x, lbl, slen)
        o2 = f2(p, x, lbl, slen)
        for a, b in zip(o1, o2):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        # the migrated seq_len multiset is preserved (slot bijection)
        assert sorted(np.asarray(o1[2]).tolist()) == \\
            sorted(np.asarray(slen).tolist())
        print("OK")
    """)
    assert "OK" in out


def test_prefill_pipeline_matches_sync_8dev():
    """Acceptance: serve_lib.prefill runs through the shared
    build/execute core with exec_mode="pipeline" (inherited chunking).
    Prefill's small per-shard capacity (~24 rows) makes XLA's CPU dot
    emitter pick a different fusion for the chunked einsums than the
    monolithic one, so sync vs pipeline agree to the last ulp region
    (≤2e-6 on f32 logits) rather than bitwise — a pre-existing backend
    artifact (the seed path reproduces it exactly; at train capacities
    the golden grid above IS bitwise). The plan objective must not
    change vanilla-mode serving outputs at all."""
    out = _run("""
        from repro import serve_lib

        cfg = dataclasses.replace(
            reduced(get_config("moe-gpt2"), num_layers=2, d_model=128),
            compute_dtype="float32")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        mesh = make_mesh((2, 4), ("data", "model"))
        B, S = 4, 64
        dist = make_dist(mesh, "prefill", B, moe_arch=True)
        assert dist.seq_axis is not None      # prefill shards the sequence
        r = np.random.default_rng(0)
        toks = jnp.asarray(r.integers(1, cfg.vocab_size, (B, S)), jnp.int32)

        def pf(luffy):
            lg, _ = jax.jit(lambda p, t: serve_lib.prefill(
                p, cfg, luffy, dist, t, S))(params, toks)
            return np.asarray(lg)

        sync = pf(LuffyConfig(enable_condensation=False,
                              enable_migration=False))
        pipe = pf(LuffyConfig(enable_condensation=False,
                              enable_migration=False,
                              exec_mode="pipeline", pipeline_chunks=3))
        ov = pf(LuffyConfig(enable_condensation=False,
                            enable_migration=False, exec_mode="pipeline",
                            pipeline_chunks=3, plan_objective="overlap"))
        np.testing.assert_allclose(sync, pipe, atol=2e-6, rtol=0)
        assert np.array_equal(pipe, ov)   # objective: same vanilla plan
        assert np.isfinite(sync).all()
        print("OK")
    """)
    assert "OK" in out
