"""Plan lifecycle (DESIGN.md §9): cross-layer reuse bit-identity +
counters on the 8-device golden grid, forced-mismatch rebuild,
ExchangePlan serialization round-trip / version rejection, the keyed
PlanCache with disk spill, and the zero-planning serving prefill."""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st   # optional dep; skips when absent

from repro.comm import CommContext
from repro.config import LuffyConfig, ModelConfig, MoEConfig
from repro.core import moe_layer as ml
from repro.core.gating import gate_apply
from repro.core.migration import home_plan, plan_migration_np
from repro.plan import (PlanCache, PlanFormatError, PlanSignature,
                        build_exchange_plan, build_plan_template,
                        estimate_planning_ms, estimate_revalidate_ms,
                        execute_plan, from_bytes, instantiate_plan,
                        next_signature, plan_key,
                        routing_signature_matches, to_bytes)
from repro.plan import exchange as pexch
from repro.plan import serial as pserial

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _mk(num_experts=4, top_k=2, shared=1):
    return ModelConfig(
        name="t", kind="decoder", family="moe", num_layers=2,
        d_model=32, d_ff=64, vocab_size=128,
        moe=MoEConfig(num_experts=num_experts, top_k=top_k, d_ff=64,
                      num_shared_experts=shared),
        layer_ffn_pattern=("moe",), compute_dtype="float32",
        param_dtype="float32")


def _single_device_plan(condense=True, capacity=256):
    from repro.models.blocks import _dtype
    cfg = _mk()
    p = ml.moe_init(jax.random.PRNGKey(0), cfg)
    r = np.random.default_rng(1)
    x = jnp.asarray(r.standard_normal((2, 16, cfg.d_model)), jnp.float32)
    sb = {"labels": jnp.zeros((2, 16), jnp.int32),
          "seq_len": jnp.full((2,), 16, jnp.int32)}
    luffy = LuffyConfig(enable_condensation=condense,
                        enable_migration=False, condense_group=16)
    xn = ml._rms(x.reshape(-1, cfg.d_model),
                 p["norm"]["scale"]).astype(_dtype(cfg.compute_dtype))
    gate = gate_apply(p["router"], xn, cfg.moe.top_k)
    plan = build_exchange_plan(
        gate, xn, cfg, luffy, CommContext.local(), mode="vanilla",
        capacity=capacity, sideband=sb, threshold=jnp.float32(0.9),
        group_size=16)
    return cfg, p, x, sb, plan


# ---------------------------------------------------------------------------
# signature helpers (host backend — shared with the traced fast path)
# ---------------------------------------------------------------------------

def test_signature_match_and_next_frame():
    r = np.random.default_rng(0)
    counts = np.floor(r.random((8, 4)) * 50).astype(np.float64)
    lens = r.permutation(np.arange(10, 18)).astype(np.float64)
    plan = plan_migration_np(counts, lens, 2)
    sig = next_signature(counts, lens, np.asarray(plan.perm))
    # the next frame observes the permuted rows -> match
    assert bool(routing_signature_matches(
        sig, np.asarray(sig.counts), np.asarray(sig.lens)))
    # any routing drift -> mismatch
    drift = np.asarray(sig.counts).copy()
    drift[0, 0] += 1.0
    assert not bool(routing_signature_matches(
        sig, drift, np.asarray(sig.lens)))
    # shape drift (different batch) -> mismatch, not an error
    assert not bool(routing_signature_matches(
        sig, np.zeros((4, 4)), np.zeros(4)))


def test_reuse_equals_replan_on_stable_frame():
    """The core reuse guarantee, host-side: when the signature matches,
    the greedy re-derives the current placement, so ``home_plan`` is
    bit-for-bit the plan a full replan would return."""
    for seed in range(10):
        rr = np.random.default_rng(seed)
        counts = np.floor(rr.random((8, 4)) * 50).astype(np.float64)
        lens = rr.permutation(np.arange(20, 28)).astype(np.float64)
        p1 = plan_migration_np(counts, lens, 2)
        sig = next_signature(counts, lens, np.asarray(p1.perm))
        c2, l2 = np.asarray(sig.counts), np.asarray(sig.lens)
        p2 = plan_migration_np(c2, l2, 2)          # what "off" would do
        hp = home_plan(c2, 2)                      # what reuse emits
        np.testing.assert_array_equal(np.asarray(p2.assign),
                                      np.asarray(hp.assign))
        np.testing.assert_array_equal(np.asarray(p2.perm),
                                      np.asarray(hp.perm))
        assert float(p2.traffic_after) == float(hp.traffic_after)
        assert float(p2.traffic_before) == float(hp.traffic_before)


def test_planning_cost_model_sane():
    assert estimate_planning_ms(64, 8) > estimate_planning_ms(16, 8) > 0
    assert estimate_revalidate_ms(64, 8) < estimate_planning_ms(64, 8)


# ---------------------------------------------------------------------------
# serialization
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("condense", [False, True])
def test_serial_roundtrip_executes_identically(condense):
    cfg, p, x, sb, plan = _single_device_plan(condense=condense)
    data = to_bytes(plan)
    plan2 = from_bytes(data)
    # static fields survive
    assert plan2.mode == plan.mode and plan2.capacity == plan.capacity
    assert plan2.chunks == plan.chunks
    assert plan2.objective == plan.objective
    assert plan2.comm.mode == plan.comm.mode
    assert (plan2.estimate is None) == (plan.estimate is None)
    assert plan2.condense == condense
    # every array field round-trips bit-exactly
    for f in pserial._ARRAY_FIELDS:
        a, b = getattr(plan, f), getattr(plan2, f)
        if a is None:
            assert b is None
        else:
            assert np.asarray(a).dtype == np.asarray(b).dtype, f
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the deserialized plan executes bit-identically
    y1, aux1 = execute_plan(p, x, dict(sb), plan, cfg)
    y2, aux2 = execute_plan(p, x, dict(sb), plan2, cfg)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    for a, b in zip(aux1.moe, aux2.moe):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_serial_rejects_version_and_magic():
    _, _, _, _, plan = _single_device_plan(condense=False)
    data = bytearray(to_bytes(plan))
    # version bump -> rejected, not misread
    bad = bytes(data[:4]) + bytes([data[4] + 1, data[5]]) + bytes(data[6:])
    with pytest.raises(PlanFormatError, match="version"):
        from_bytes(bad)
    # foreign magic -> rejected
    with pytest.raises(PlanFormatError, match="magic"):
        from_bytes(b"NOPE" + bytes(data[4:]))
    # truncated payload -> rejected
    with pytest.raises(PlanFormatError):
        from_bytes(bytes(data[:-8]))


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_serial_roundtrip_property(data):
    """to_bytes ∘ from_bytes identity across dtypes/shapes for the
    traced-array payload (signature + routing fields)."""
    shape = data.draw(st.tuples(st.integers(1, 7), st.integers(1, 5)),
                      label="shape")
    dtype = data.draw(st.sampled_from(
        ["float32", "int32", "bfloat16", "bool"]), label="dtype")
    r = np.random.default_rng(data.draw(st.integers(0, 2**31 - 1)))
    n, k = shape
    raw = r.standard_normal((n, k)) * 8
    if dtype == "bool":
        arr = jnp.asarray(raw > 0)
    else:
        arr = jnp.asarray(raw).astype(jnp.dtype(dtype))
    _, _, _, _, plan = _single_device_plan(condense=False)
    sig = PlanSignature(arr, jnp.arange(n, dtype=jnp.float32),
                        jnp.float32(1.0))
    plan = plan._replace(signature=sig,
                         gate_weights=arr.astype(jnp.float32)
                         if dtype == "bool" else arr)
    plan2 = from_bytes(to_bytes(plan))
    np.testing.assert_array_equal(np.asarray(plan2.signature.counts),
                                  np.asarray(arr))
    assert np.asarray(plan2.signature.counts).dtype == \
        np.asarray(arr).dtype
    np.testing.assert_array_equal(np.asarray(plan2.gate_weights),
                                  np.asarray(plan.gate_weights))


# ---------------------------------------------------------------------------
# PlanCache
# ---------------------------------------------------------------------------

def test_plan_cache_memory_disk_and_eviction(tmp_path):
    cfg = _mk()
    luffy = LuffyConfig(enable_condensation=False, enable_migration=False)
    cache = PlanCache(tmp_path, mem_capacity=2)
    keys = []
    for n_seq in (1, 2, 4):
        key = plan_key(n_seq=n_seq, seq_len=16, d_model=cfg.d_model,
                       capacity=64, top_k=2, num_experts=4,
                       mode="vanilla", objective="traffic",
                       exec_mode="sync", pipeline_chunks=1,
                       comm_mode="local", topo=None, M=1)
        tmpl = build_plan_template(cfg, luffy, n_seq=n_seq, seq_len=16,
                                   capacity=64)
        cache.put(key, tmpl)
        keys.append(key)
    # LRU evicted the first entry from memory but its spill file remains
    assert len(cache) == 2
    assert (tmp_path / f"{keys[0]}.plan").exists()
    got = cache.get(keys[0])
    assert got is not None and got.capacity == 64
    assert cache.disk_loads == 1
    # a cold cache over the same directory serves all entries from disk
    cold = PlanCache(tmp_path)
    for k in keys:
        assert cold.get(k) is not None
    assert cold.disk_loads == 3
    # corrupt file -> miss, never a wrong plan
    (tmp_path / f"{keys[1]}.plan").write_bytes(b"garbage")
    assert PlanCache(tmp_path).get(keys[1]) is None
    # distinct shapes never collide
    assert len(set(keys)) == 3


def test_template_instantiate_matches_build_single_device():
    from repro.models.blocks import _dtype
    cfg = _mk()
    p = ml.moe_init(jax.random.PRNGKey(0), cfg)
    r = np.random.default_rng(7)
    x = jnp.asarray(r.standard_normal((2, 16, cfg.d_model)), jnp.float32)
    sb = {"labels": jnp.zeros((2, 16), jnp.int32),
          "seq_len": jnp.asarray([12, 16], jnp.int32)}
    nl = LuffyConfig(enable_condensation=False, enable_migration=False)
    comm = CommContext.local()
    xn = ml._rms(x.reshape(-1, cfg.d_model),
                 p["norm"]["scale"]).astype(_dtype(cfg.compute_dtype))
    gate = gate_apply(p["router"], xn, cfg.moe.top_k)
    built = build_exchange_plan(gate, xn, cfg, nl, comm, mode="vanilla",
                                capacity=64, sideband=sb)
    tmpl = from_bytes(to_bytes(build_plan_template(
        cfg, nl, n_seq=2, seq_len=16, capacity=64)))
    inst = instantiate_plan(tmpl, gate, xn, cfg, comm, capacity=64,
                            sideband=sb)
    assert inst.chunks == built.chunks and inst.pipelined == built.pipelined
    y1, _ = execute_plan(p, x, dict(sb), built, cfg)
    y2, _ = execute_plan(p, x, dict(sb), inst, cfg)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))


def test_prefill_warm_cache_zero_planning_calls(tmp_path):
    """Acceptance: a warm PlanCache prefill performs ZERO
    build_exchange_plan calls (every MoE sublayer instantiates the
    cached template) and its logits are bit-identical to the uncached
    forward."""
    from repro import serve_lib
    from repro.configs import get_config
    from repro.config import reduced
    from repro.dist import single_device
    from repro.models.model import build_model
    from repro.plan.cache import precompute_prefill_plans

    cfg = dataclasses.replace(
        reduced(get_config("moe-gpt2"), num_layers=2, d_model=64),
        compute_dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    dist = single_device()
    nl = LuffyConfig(enable_condensation=False, enable_migration=False)
    B, S = 2, 32
    r = np.random.default_rng(0)
    toks = jnp.asarray(r.integers(1, cfg.vocab_size, (B, S)), jnp.int32)

    cache = PlanCache(tmp_path)
    key = precompute_prefill_plans(cfg, nl, dist, B, S, cache)
    assert cache.get(key) is not None

    base = pexch.BUILD_CALLS
    cold = jax.jit(lambda p, t: serve_lib.prefill(
        p, cfg, nl, dist, t, S)[0]).lower(params, toks)
    built_cold = pexch.BUILD_CALLS - base
    # one build per MoE pattern position (the layer scan traces once)
    assert built_cold == 1

    base = pexch.BUILD_CALLS
    warm = jax.jit(lambda p, t: serve_lib.prefill(
        p, cfg, nl, dist, t, S, plan_cache=cache)[0]).lower(params, toks)
    assert pexch.BUILD_CALLS - base == 0   # zero planning on request path
    assert cache.hits >= 1

    lg_cold = np.asarray(cold.compile()(params, toks))
    lg_warm = np.asarray(warm.compile()(params, toks))
    np.testing.assert_array_equal(lg_cold, lg_warm)
    assert np.isfinite(lg_cold).all()


# ---------------------------------------------------------------------------
# 8-device golden grid (subprocesses, like test_plan/test_comm)
# ---------------------------------------------------------------------------

def _run(script_body: str) -> str:
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses
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


def test_plan_reuse_golden_grid_8dev():
    """Acceptance (ISSUE 4): on the 8-device golden grid,
    plan_reuse="signature" is bit-identical to "off" both when routing
    drifts (revalidation fails, stale plans are rebuilt) and when
    routing is stable (the full-replan count per forward drops from
    one-per-MoE-sublayer to 1, asserted via the plan_reuse ledger);
    "always" trusts the carry and still trains to a finite loss."""
    out = _run("""
        cfg = reduced(get_config("moe-gpt2"), num_layers=3, d_model=128)
        cfg = dataclasses.replace(cfg, compute_dtype="float32")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        shape = ShapeConfig("t", 64, 16, "train")
        data = SyntheticLM(cfg, shape)
        b = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
        # strictly distinct lengths: the greedy's order is tie-free, so
        # its per-sequence decisions are frame-invariant (DESIGN.md §9)
        b["seq_len"] = jnp.asarray(
            np.random.default_rng(0).permutation(np.arange(48, 64)),
            jnp.int32)
        cap = capacity_for(cfg.moe, 64, cfg.moe.num_experts, slack=8.0)
        mesh = make_mesh((2, 2, 2), ("data", "node", "local"))
        dist = DistContext(mesh, batch_axes=("data", "node", "local"),
                           seq_axis=None, fsdp_axes=("data",),
                           model_axis=("node", "local"),
                           topology=Topology(2, 2))

        def loss(params, luffy):
            l, m = jax.jit(lambda p, bb: model.train_loss(
                p, bb, jnp.float32(0.4), luffy=luffy, dist=dist,
                capacity=cap))(params, b)
            return float(l), {k: float(v) for k, v in m.items()}

        COUNTERS = ("plans_built", "plans_reused", "plan_reuse_mismatch")
        base = LuffyConfig(enable_condensation=False,
                           enable_migration=True, combine_slack=4.0,
                           condense_group=32)

        # -- drifting routing: per-layer routers differ, reuse never
        # fires, every sublayer replans -> bit-identical by graph parity
        l0, m0 = loss(params, base)
        l1, m1 = loss(params,
                      dataclasses.replace(base, plan_reuse="signature"))
        assert l0 == l1, (l0, l1)
        for k in m0:
            if k not in COUNTERS:
                assert m0[k] == m1[k], (k, m0[k], m1[k])
        assert m0["plans_built"] == 3.0 and m1["plans_built"] == 3.0
        assert m1["plans_reused"] == 0.0
        # forced mismatch: the stale carried plan was rebuilt, not
        # silently executed — one mismatch per post-seed sublayer
        assert m1["plan_reuse_mismatch"] == 2.0, m1

        # -- stable routing (zeroed routers: top-k ties resolve to the
        # same experts for every token at every layer): plan once,
        # execute N times, still bit-identical to "off"
        stable = dict(params)
        stable["layers"] = [dict(params["layers"][0])]
        stable["layers"][0]["moe"] = dict(params["layers"][0]["moe"])
        stable["layers"][0]["moe"]["router"] = {
            "w_gate": jnp.zeros_like(
                params["layers"][0]["moe"]["router"]["w_gate"])}
        l2, m2 = loss(stable, base)
        l3, m3 = loss(stable,
                      dataclasses.replace(base, plan_reuse="signature"))
        assert l2 == l3, (l2, l3)
        for k in m2:
            if k not in COUNTERS:
                assert m2[k] == m3[k], (k, m2[k], m3[k])
        assert m2["plans_built"] == 3.0            # off: one per sublayer
        assert m3["plans_built"] == 1.0, m3        # signature: plan ONCE
        assert m3["plans_reused"] == 2.0
        assert m3["plan_reuse_mismatch"] == 0.0

        # -- "always": trusts the carry without revalidation
        l4, m4 = loss(stable,
                      dataclasses.replace(base, plan_reuse="always"))
        assert np.isfinite(l4)
        assert m4["plans_built"] == 1.0 and m4["plans_reused"] == 2.0

        # -- "overlap" objective: the portfolio may execute a plan the
        # pure greedy would not re-derive, so reuse must stay disabled
        # (carry never validates) while graph parity keeps the modes
        # bit-identical
        ovl = dataclasses.replace(base, plan_objective="overlap")
        l7, m7 = loss(stable, ovl)
        l8, m8 = loss(stable,
                      dataclasses.replace(ovl, plan_reuse="signature"))
        assert l7 == l8, (l7, l8)
        for k in m7:
            assert m7[k] == m8[k], (k, m7[k], m8[k])
        assert m8["plans_built"] == 3.0 and m8["plans_reused"] == 0.0

        # -- condensation on: rep-map rebuilt per sublayer changes the
        # routing signature, so reuse must revalidate (never silently
        # execute a stale plan) and stay bit-identical to "off"
        cond = dataclasses.replace(base, enable_condensation=True)
        l5, m5 = loss(params, cond)
        l6, m6 = loss(params,
                      dataclasses.replace(cond, plan_reuse="signature"))
        assert l5 == l6, (l5, l6)
        for k in m5:
            if k not in COUNTERS:
                assert m5[k] == m6[k], (k, m5[k], m6[k])
        assert m6["plans_built"] + m6["plans_reused"] == 3.0
        print("OK")
    """)
    assert "OK" in out


def test_objective_planned_chunk_count_8dev():
    """Satellite: pipeline_chunks=0 lets build_exchange_plan pick
    ChunkPlan.n_chunks from estimate_exchange(chunks=None)'s search;
    an explicit positive value still overrides."""
    out = _run("""
        from repro.core import moe_layer as ml
        from repro.core.gating import gate_apply
        from repro.plan import build_exchange_plan, estimate_exchange
        from repro.models.blocks import _dtype

        cfg = dataclasses.replace(
            reduced(get_config("moe-gpt2"), num_layers=2, d_model=64),
            compute_dtype="float32")
        p = ml.moe_init(jax.random.PRNGKey(1), cfg)
        mesh = make_mesh((2, 2, 2), ("data", "node", "local"))
        topo = Topology(2, 2)
        comm = CommContext.build("hier", ("node", "local"), topo)
        n_seq, S, d = 2, 32, cfg.d_model
        cap = ml.capacity_for(cfg.moe, n_seq * S, cfg.moe.num_experts,
                              slack=4.0)
        r = np.random.default_rng(0)
        x = jnp.asarray(r.standard_normal((16, S, d)), jnp.float32)
        lbl = jnp.zeros((16, S), jnp.int32)
        slen = jnp.full((16,), S, jnp.int32)

        def chunks_for(luffy):
            def inner(p_l, x_l, lbl_l, sl_l):
                sb = {"labels": lbl_l, "seq_len": sl_l}
                xn = ml._rms(x_l.reshape(-1, d), p_l["norm"]["scale"]
                             ).astype(_dtype(cfg.compute_dtype))
                gate = gate_apply(p_l["router"], xn, cfg.moe.top_k)
                plan = build_exchange_plan(
                    gate, xn, cfg, luffy, comm, mode="vanilla",
                    capacity=cap, sideband=sb)
                inner.n_chunks = plan.chunks.n_chunks
                return x_l
            ba = ("data", "node", "local")
            p_specs = jax.tree.map(lambda _: P(), p)
            p_specs["experts"] = jax.tree.map(
                lambda _: P(("node", "local"), None, None), p["experts"])
            jax.jit(shard_map(
                inner, mesh=mesh,
                in_specs=(p_specs, P(ba, None, None), P(ba, None), P(ba)),
                out_specs=P(ba, None, None))).lower(p, x, lbl, slen)
            return inner.n_chunks

        auto = LuffyConfig(enable_condensation=False,
                           enable_migration=False, exec_mode="pipeline",
                           pipeline_chunks=0, plan_objective="overlap")
        fixed = dataclasses.replace(auto, pipeline_chunks=2)
        # the planned count == the estimate search at this shape
        T = n_seq * S
        want = estimate_exchange(
            T, cfg.moe.top_k, d, topo=topo, bytes_per_el=4,
            ffn_ms=cfg.moe.num_experts * cap * 4.0 * d * cfg.moe.d_ff
            / auto.gpu_speed * 1e3, chunks=None).chunks
        from repro.sched import plan_chunks
        assert chunks_for(auto) == plan_chunks(cap, want).n_chunks, \\
            (chunks_for(auto), want)
        assert chunks_for(fixed) == plan_chunks(cap, 2).n_chunks
        print("OK")
    """)
    assert "OK" in out
