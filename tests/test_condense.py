"""repro.condense (DESIGN.md §10): the similarity-backend registry
("exact" == legacy bit-for-bit, "lsh" measures strictly fewer pairs with
full recall on identical tokens), condense-plan reuse (signature
revalidation + staleness bound, builds drop to 1 per forward), the
deduplicated hier wire (dispatch reconstruction bit-identical, combine
within tolerance, shipped == modeled bytes) and the serial-format /
PlanCache params_version bump."""
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
from repro.condense import (CondenseCarry, available_similarity_backends,
                            condense_tokens, expected_measured_pairs,
                            fast_similarity, get_similarity_backend,
                            lsh_codes)
from repro.condense import backends as cbk
from repro.config import LuffyConfig, ModelConfig, MoEConfig
from repro.core import moe_layer as ml
from repro.core.gating import gate_apply
from repro.plan import (PlanCache, PlanFormatError, from_bytes,
                        build_exchange_plan, execute_plan, plan_key,
                        to_bytes)

ROOT = os.path.join(os.path.dirname(__file__), "..")


# ---------------------------------------------------------------------------
# backend registry
# ---------------------------------------------------------------------------

def test_registry_lookup_and_error():
    assert set(available_similarity_backends()) >= {"exact", "lsh"}
    assert get_similarity_backend("exact") is cbk.exact_backend
    with pytest.raises(ValueError, match="exact"):
        get_similarity_backend("nope")


def test_registry_extensible():
    @cbk.register_similarity_backend("_test_none")
    def none_backend(x, uncertain, *, use_kernel=False, lsh_bits=8,
                     lsh_seed=0):
        G = x.shape[0]
        eye = jnp.eye(G, dtype=bool)
        return jnp.where(eye, 1.0, 0.0), eye

    try:
        sim, measured = fast_similarity(
            jnp.ones((8, 4), jnp.float32), jnp.zeros((8,), jnp.int32),
            None, 0.8, 0.2, backend="_test_none")
        # only the diagonal was measured
        assert float(measured) == pytest.approx(1.0 / 8)
    finally:
        cbk.SIMILARITY_BACKENDS.pop("_test_none")


def test_exact_backend_reproduces_legacy_skip_rules(rng):
    """The registry's "exact" entry is the historical §V-A path: the
    masked values equal pairwise_cosine under the skip-rule masks."""
    G, d = 32, 16
    x = jnp.asarray(rng.standard_normal((G, d)), jnp.float32)
    e = jnp.asarray(rng.integers(0, 2, G))
    s_prev = jnp.asarray(rng.random((G, G)), jnp.float32)
    sim, measured = fast_similarity(x, e, s_prev, 0.8, 0.2,
                                    backend="exact")
    same = np.asarray(e)[:, None] == np.asarray(e)[None, :]
    sp = np.asarray(s_prev)
    s = np.asarray(sim)
    cos = np.asarray(cbk.pairwise_cosine(x))
    uncertain = same & ~(sp > 0.8) & ~(sp < 0.2)
    np.testing.assert_array_equal(s[uncertain], cos[uncertain])
    assert (s[~same] == 0).all()
    assert (s[same & (sp > 0.8)] == 1.0).all()
    assert float(measured) == pytest.approx(uncertain.mean())


def test_lsh_measures_strictly_fewer_pairs_on_random_tokens(rng):
    G, d = 256, 64
    x = jnp.asarray(rng.standard_normal((G, d)), jnp.float32)
    e = jnp.asarray(rng.integers(0, 4, G), jnp.int32)
    a = condense_tokens(x, e, 0.9, group_size=G, backend="exact")
    b = condense_tokens(x, e, 0.9, group_size=G, backend="lsh")
    assert float(b.measured_pairs) < float(a.measured_pairs)
    # codes are deterministic (fixed host-side projections)
    np.testing.assert_array_equal(np.asarray(lsh_codes(x)),
                                  np.asarray(lsh_codes(x)))


def test_lsh_identical_tokens_condense_like_exact():
    """Duplicate-heavy groups: identical tokens always share a bucket,
    so the LSH backend condenses them at exactly the exact rate."""
    G, d = 32, 16
    uniq = np.eye(G // 4, d, dtype=np.float32)        # orthogonal uniques
    x = jnp.asarray(np.repeat(uniq, 4, axis=0))       # 4 clones each
    e = jnp.asarray(np.repeat(np.arange(G // 4) % 2, 4), jnp.int32)
    a = condense_tokens(x, e, 0.9, group_size=G, backend="exact")
    b = condense_tokens(x, e, 0.9, group_size=G, backend="lsh",
                        lsh_bits=8)
    np.testing.assert_array_equal(np.asarray(a.rep_idx),
                                  np.asarray(b.rep_idx))
    assert float(a.rate) == float(b.rate) == 0.75


def test_expected_measured_pairs_model():
    ex = expected_measured_pairs(1024, 128, 8, backend="exact")
    ls = expected_measured_pairs(1024, 128, 8, backend="lsh", lsh_bits=8)
    assert 0 < ls < ex
    with pytest.raises(ValueError):
        expected_measured_pairs(1024, 128, 8, backend="nope")


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1), st.sampled_from([16, 32]),
       st.sampled_from([4, 8]))
def test_lsh_identical_token_recall_property(seed, G, bits):
    """Property: on groups built from orthogonal uniques + exact clones,
    LSH reps == exact reps for any seed/bits (identical tokens collide
    with probability 1)."""
    r = np.random.default_rng(seed)
    n_uniq = G // 4
    uniq = np.eye(n_uniq, 24, dtype=np.float32) * (1 + r.random(1))
    x = jnp.asarray(np.repeat(uniq, 4, axis=0))
    e = jnp.asarray(np.repeat(r.integers(0, 3, n_uniq), 4), jnp.int32)
    a = condense_tokens(x, e, 0.9, group_size=G, backend="exact")
    b = condense_tokens(x, e, 0.9, group_size=G, backend="lsh",
                        lsh_bits=bits, lsh_seed=seed % 7)
    np.testing.assert_array_equal(np.asarray(a.rep_idx),
                                  np.asarray(b.rep_idx))


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1), st.sampled_from([4, 6, 8]))
def test_lsh_perturbed_clone_recall_property(seed, bits):
    """Property: small perturbations rarely flip projection signs — the
    fraction of (token, clone) pairs the LSH backend still measures
    stays above the recall floor."""
    r = np.random.default_rng(seed)
    G, d = 64, 32
    base = r.standard_normal((G // 2, d)).astype(np.float32)
    clones = base + 0.01 * r.standard_normal((G // 2, d)).astype(
        np.float32) * np.abs(base).mean()
    x = jnp.asarray(np.concatenate([base, clones], 0))
    codes = np.asarray(lsh_codes(x, bits=bits, seed=0))
    recall = float(np.mean(codes[:G // 2] == codes[G // 2:]))
    assert recall >= 0.6, (seed, bits, recall)


# ---------------------------------------------------------------------------
# condense-plan reuse (single device; the 8-dev golden test is below)
# ---------------------------------------------------------------------------

def _mk(num_experts=4, top_k=2):
    return ModelConfig(
        name="t", kind="decoder", family="moe", num_layers=2,
        d_model=32, d_ff=64, vocab_size=128,
        moe=MoEConfig(num_experts=num_experts, top_k=top_k, d_ff=64),
        layer_ffn_pattern=("moe",), compute_dtype="float32",
        param_dtype="float32")


def _plan_with_carry(luffy, carry, s_prev, threshold=0.7, seed=1):
    from repro.models.blocks import _dtype
    cfg = _mk()
    p = ml.moe_init(jax.random.PRNGKey(0), cfg)
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.standard_normal((2, 16, cfg.d_model)), jnp.float32)
    sb = {"labels": jnp.zeros((2, 16), jnp.int32),
          "seq_len": jnp.full((2,), 16, jnp.int32)}
    xn = ml._rms(x.reshape(-1, cfg.d_model),
                 p["norm"]["scale"]).astype(_dtype(cfg.compute_dtype))
    gate = gate_apply(p["router"], xn, cfg.moe.top_k)
    plan = build_exchange_plan(
        gate, xn, cfg, luffy, CommContext.local(), mode="vanilla",
        capacity=256, sideband=sb, threshold=jnp.float32(threshold),
        group_size=16, s_prev=s_prev, condense_reuse_from=carry)
    return cfg, p, x, sb, plan


def _zero_carry(T=32, n_seq=2):
    return CondenseCarry(jnp.zeros((T,), jnp.int32),
                         jnp.zeros((T,), jnp.int32),
                         jnp.zeros((n_seq,), jnp.float32),
                         jnp.zeros((n_seq,), jnp.float32))


def test_condense_reuse_matches_rebuild_on_stable_frame():
    """The reuse guarantee at the API level: revalidating against the
    exact frame the plan was built on emits a rep map bit-identical to
    a full rebuild (same deterministic inputs), with the similarity
    build skipped (measured_pairs == 0, reused counter set)."""
    luffy = LuffyConfig(enable_condensation=True, enable_migration=False,
                        condense_group=16, condense_reuse="signature")
    s_prev = jnp.full((2, 16, 16), 0.5, jnp.float32)
    cfg, p, x, sb, p1 = _plan_with_carry(luffy, _zero_carry(), s_prev)
    assert float(p1.condense_plan.built) == 1.0      # seed layer builds
    _, aux1 = execute_plan(p, x, dict(sb), p1, cfg)
    cc = aux1.cond_carry
    assert cc is not None
    carry = CondenseCarry(cc["rep"].reshape(-1), cc["cexp"].reshape(-1),
                          cc["age"], cc["valid"])
    _, _, _, _, p2 = _plan_with_carry(luffy, carry, p1.s_next)
    cp = p2.condense_plan
    assert float(cp.reused) == 1.0 and float(cp.built) == 0.0
    assert float(cp.measured_pairs) == 0.0
    nl = dataclasses.replace(luffy, condense_reuse="off")
    _, _, _, _, p2f = _plan_with_carry(nl, None, p1.s_next)
    np.testing.assert_array_equal(np.asarray(p2.rep_idx),
                                  np.asarray(p2f.rep_idx))


def test_condense_reuse_staleness_and_expert_drift():
    luffy = LuffyConfig(enable_condensation=True, enable_migration=False,
                        condense_group=16, condense_reuse="signature",
                        condense_reuse_max_age=1)
    s_prev = jnp.full((2, 16, 16), 0.5, jnp.float32)
    cfg, p, x, sb, p1 = _plan_with_carry(luffy, _zero_carry(), s_prev)
    _, aux1 = execute_plan(p, x, dict(sb), p1, cfg)
    cc = aux1.cond_carry
    carry = CondenseCarry(cc["rep"].reshape(-1), cc["cexp"].reshape(-1),
                          cc["age"], cc["valid"])
    # age at the bound: the carried plan is stale, a rebuild runs
    old = carry._replace(age=jnp.full((2,), 1.0, jnp.float32))
    _, _, _, _, p2 = _plan_with_carry(luffy, old, p1.s_next)
    assert float(p2.condense_plan.built) == 1.0
    # expert drift: merged tokens no longer share an expert -> rebuild
    drift = carry._replace(expert=carry.expert + 1)
    _, _, _, _, p3 = _plan_with_carry(luffy, drift, p1.s_next)
    assert float(p3.condense_plan.built) == 1.0
    # "off" pins the EMITTED valid flag (like migration plan_reuse, the
    # pin is at emission): within an "off" stack the carry never
    # revalidates, so every sublayer rebuilds with the same graph
    off = LuffyConfig(enable_condensation=True, enable_migration=False,
                      condense_group=16, condense_reuse="off")
    _, _, _, _, p4 = _plan_with_carry(off, _zero_carry(), s_prev)
    assert float(p4.condense_plan.built) == 1.0
    assert float(jnp.max(p4.condense_plan.signature.valid)) == 0.0
    sig4 = p4.condense_plan.signature
    off_carry = CondenseCarry(p4.condense_plan.rep_idx % 16, sig4.expert,
                              sig4.age, sig4.valid)
    _, _, _, _, p4b = _plan_with_carry(off, off_carry, p4.s_next)
    assert float(p4b.condense_plan.built) == 1.0
    # "always" skips the expert compare (age bound still applies)
    alw = LuffyConfig(enable_condensation=True, enable_migration=False,
                      condense_group=16, condense_reuse="always")
    _, _, _, _, p5 = _plan_with_carry(alw, drift, p1.s_next)
    assert float(p5.condense_plan.reused) == 1.0


# ---------------------------------------------------------------------------
# serial format v2 + PlanCache params_version (ISSUE 5 satellite)
# ---------------------------------------------------------------------------

def _vanilla_plan():
    luffy = LuffyConfig(enable_condensation=True, enable_migration=False,
                        condense_group=16)
    return _plan_with_carry(luffy, None, None)


def test_serial_rejects_v1_blobs():
    """Old-format blobs (pre-CondensePlan layout) are rejected with
    PlanFormatError, never misread."""
    import struct
    _, _, _, _, plan = _vanilla_plan()
    data = bytearray(to_bytes(plan))
    v1 = bytes(data[:4]) + struct.pack("<H", 1) + bytes(data[6:])
    with pytest.raises(PlanFormatError, match="version 1"):
        from_bytes(v1)


def test_serial_condense_plan_roundtrip():
    luffy = LuffyConfig(enable_condensation=True, enable_migration=False,
                        condense_group=16, condense_reuse="signature")
    s_prev = jnp.full((2, 16, 16), 0.5, jnp.float32)
    cfg, p, x, sb, plan = _plan_with_carry(luffy, _zero_carry(), s_prev)
    plan2 = from_bytes(to_bytes(plan))
    cp, cp2 = plan.condense_plan, plan2.condense_plan
    assert cp2.backend == cp.backend
    for f in ("rep_idx", "is_rep", "s_next", "rate", "measured_pairs",
              "built", "reused"):
        np.testing.assert_array_equal(np.asarray(getattr(cp, f)),
                                      np.asarray(getattr(cp2, f)))
    for f in ("expert", "age", "valid"):
        np.testing.assert_array_equal(
            np.asarray(getattr(cp.signature, f)),
            np.asarray(getattr(cp2.signature, f)))
    assert plan2.wire == plan.wire
    y1, _ = execute_plan(p, x, dict(sb), plan, cfg)
    y2, _ = execute_plan(p, x, dict(sb), plan2, cfg)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))


def test_serial_params_version_gate():
    _, _, _, _, plan = _vanilla_plan()
    data = to_bytes(plan, params_version="step42")
    assert from_bytes(data, expect_params_version="step42") is not None
    from_bytes(data)                         # no expectation: accepted
    with pytest.raises(PlanFormatError, match="params_version"):
        from_bytes(data, expect_params_version="step43")


def test_plan_cache_params_version_never_trusts_stale(tmp_path):
    """A cache at a newer router fingerprint treats blobs written at an
    older one as misses (rebuilt, never trusted)."""
    from repro.plan import build_plan_template
    cfg = _mk()
    luffy = LuffyConfig(enable_condensation=False, enable_migration=False)
    tmpl = build_plan_template(cfg, luffy, n_seq=2, seq_len=16,
                               capacity=64)
    key = "shared_key"
    old = PlanCache(tmp_path, params_version="step1")
    old.put(key, tmpl)
    assert PlanCache(tmp_path, params_version="step1").get(key) is not None
    assert PlanCache(tmp_path, params_version="step2").get(key) is None
    # and the key itself separates versions/wire formats
    base = dict(n_seq=2, seq_len=16, d_model=32, capacity=64, top_k=2,
                num_experts=4, mode="migrate", objective="traffic",
                exec_mode="sync", pipeline_chunks=1, comm_mode="hier",
                topo=None, M=8)
    k1 = plan_key(**base, params_version="step1")
    k2 = plan_key(**base, params_version="step2")
    k3 = plan_key(**base, params_version="step1", hier_dedup="on")
    assert len({k1, k2, k3}) == 3


# ---------------------------------------------------------------------------
# dedup wire: 8-device round-trips + golden grid (subprocess, like
# test_sideband / test_plan)
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


def test_dedup_wire_roundtrip_8dev():
    """Bijection of the dedup wire: the reconstructed dispatch rows are
    bit-identical to the dense wire's, the combine round trip matches
    the dense per-token sums within float tolerance, and the shipped
    inter-node row count equals the ledger's distinct-(token, node)
    model exactly."""
    out = _run("""
        from repro.comm import ledger as comm_ledger
        from repro.condense.wire import dedup_combine, dedup_dispatch
        from repro.core.gating import dispatch_positions

        N, L = 2, 4
        M = N * L
        mesh = make_mesh((N, L), ("node", "local"))
        topo = Topology(N, L)
        comm = CommContext.build("hier", ("node", "local"), topo)
        T, k, d, E_local, C = 48, 2, 16, 2, 24
        E = E_local * M
        r = np.random.default_rng(0)
        xf = r.standard_normal((M, T, d)).astype(np.float32)
        expert_idx = r.integers(0, E, (M, T, k)).astype(np.int32)
        gate_w = r.random((M, T, k)).astype(np.float32)

        def inner(xf_l, e_l, g_l):
            xf_l, e_l, g_l = xf_l[0], e_l[0], g_l[0]   # drop shard dim
            keep = jnp.ones((T, k), bool)
            pos = dispatch_positions(e_l, keep, E)
            valid = keep & (pos < C)
            my = comm.index()
            # dense reference: payload [x, gw] through the dense wire
            pay = jnp.concatenate([
                jnp.tile(xf_l[:, None], (1, k, 1)),
                g_l[..., None]], -1).reshape(-1, d + 1)
            v_f = valid.reshape(-1)
            e_s = jnp.where(v_f, e_l.reshape(-1), 0)
            p_s = jnp.where(v_f, pos.reshape(-1), 0)
            buf = jnp.zeros((E, C, d + 1), jnp.float32).at[e_s, p_s].add(
                pay * v_f[:, None], mode="drop")
            buf = comm.all_to_all(buf)
            rows = buf.reshape(M, E_local, C, d + 1).transpose(1, 0, 2, 3)
            x_rows, gw_rows, rvalid, state = dedup_dispatch(
                xf_l, e_l, g_l, valid, pos, comm=comm,
                e_local=E_local, capacity=C)
            # combine: fake per-row expert output = 3*x, gate-weighted
            out_rows = 3.0 * x_rows * gw_rows[..., None]
            delta = dedup_combine(out_rows, state, comm=comm)
            # dense combine reference
            dr = 3.0 * rows[..., :d] * rows[..., d:]
            back = dr.reshape(E_local, M, C, d).transpose(1, 0, 2, 3) \
                     .reshape(E, C, d)
            back = comm.combine(back)
            vals = back[e_s, p_s] * v_f[:, None]
            dense_delta = jnp.sum(vals.reshape(T, k, d), axis=1)
            _, dedup_model = comm_ledger.dispatch_node_ledger(
                e_l, valid, my, e_local=E_local, topo=topo, row_bytes=1.0)
            return tuple(jnp.asarray(a)[None] for a in (
                x_rows, rows[..., :d], gw_rows, rows[..., d],
                delta, dense_delta, state["shipped_rows"], dedup_model))

        fn = shard_map(inner, mesh=mesh,
                       in_specs=(P(("node", "local")),) * 3,
                       out_specs=(P(("node", "local")),) * 8)
        (xr, xd, gr, gd, delta, dense, shipped, model) = fn(
            jnp.asarray(xf), jnp.asarray(expert_idx), jnp.asarray(gate_w))
        assert np.array_equal(np.asarray(xr), np.asarray(xd)), "x rows"
        assert np.array_equal(np.asarray(gr), np.asarray(gd)), "gate rows"
        np.testing.assert_allclose(np.asarray(delta), np.asarray(dense),
                                   rtol=0, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(shipped),
                                      np.asarray(model))
        assert float(np.asarray(shipped).sum()) > 0
        print("OK")
    """)
    assert "OK" in out


def test_dedup_migrate_roundtrip_8dev():
    """Migrate-frame bijection roundtrip (ISSUE 10): the dest-keyed
    re-expansion map survives the wire exactly — the ``dgpos``/``prim``
    planes reconstruct bit-identically to a dense map exchange — and
    :func:`dedup_combine_migrate` lands every token's materialized row
    (``y·gw + x·prim``) at its post-migration home within float
    tolerance of a host-side dense reference. The migration permutation
    is a bijection on global slots, so every destination receives
    exactly T rows."""
    out = _run("""
        from repro.condense.wire import (dedup_combine_migrate,
                                         dedup_dispatch)
        from repro.core.gating import dispatch_positions

        N, L = 2, 4
        M = N * L
        mesh = make_mesh((N, L), ("node", "local"))
        topo = Topology(N, L)
        comm = CommContext.build("hier", ("node", "local"), topo)
        T, k, d, E_local, C = 48, 2, 16, 2, 24
        E = E_local * M
        r = np.random.default_rng(1)
        xf = r.standard_normal((M, T, d)).astype(np.float32)
        expert_idx = r.integers(0, E, (M, T, k)).astype(np.int32)
        gate_w = r.random((M, T, k)).astype(np.float32)
        SHIFT = 3        # cyclic device shift: a slot bijection

        def inner(xf_l, e_l, g_l):
            xf_l, e_l, g_l = xf_l[0], e_l[0], g_l[0]
            keep = jnp.ones((T, k), bool)
            pos = dispatch_positions(e_l, keep, E)
            valid = keep & (pos < C)
            my = comm.index()
            dest_dev = (my + SHIFT) % M        # position-preserving
            dest_gpos = dest_dev * T + jnp.arange(T, dtype=jnp.int32)
            prim = jnp.broadcast_to(
                (jnp.arange(k) == 0)[None, :], (T, k)) \
                .astype(jnp.float32)
            x_rows, gw_rows, rvalid, state = dedup_dispatch(
                xf_l, e_l, g_l, valid, pos, comm=comm,
                e_local=E_local, capacity=C,
                dest_gpos=dest_gpos, prim=prim)
            # fake expert: 3*x, gate-weighted + primary-copy residual
            out_rows = (3.0 * x_rows * gw_rows[..., None]
                        + x_rows * state["prim"][..., None])
            y = dedup_combine_migrate(out_rows, state, comm=comm)
            # dense map reference: the (dgpos+1, prim) planes through
            # the ordinary dense exchange
            pay = jnp.concatenate([
                jnp.broadcast_to(
                    dest_gpos.astype(jnp.float32)[:, None, None] + 1.0,
                    (T, k, 1)),
                prim[..., None]], -1).reshape(-1, 2)
            v_f = valid.reshape(-1)
            e_s = jnp.where(v_f, e_l.reshape(-1), 0)
            p_s = jnp.where(v_f, pos.reshape(-1), 0)
            buf = jnp.zeros((E, C, 2), jnp.float32).at[e_s, p_s].add(
                pay * v_f[:, None], mode="drop")
            buf = comm.all_to_all(buf)
            rmeta = buf.reshape(M, E_local, C, 2).transpose(1, 0, 2, 3)
            dg_want = jnp.round(rmeta[..., 0]).astype(jnp.int32) - 1
            return tuple(jnp.asarray(a)[None] for a in (
                y, state["dgpos"], dg_want, state["prim"],
                rmeta[..., 1], valid))

        fn = shard_map(inner, mesh=mesh,
                       in_specs=(P(("node", "local")),) * 3,
                       out_specs=(P(("node", "local")),) * 6)
        y, dg, dg_want, pr, pr_want, valid = fn(
            jnp.asarray(xf), jnp.asarray(expert_idx),
            jnp.asarray(gate_w))
        # exact map roundtrip: bit-identical to the dense exchange
        np.testing.assert_array_equal(np.asarray(dg), np.asarray(dg_want))
        np.testing.assert_array_equal(np.asarray(pr), np.asarray(pr_want))
        # host-side dense migrate reference, permuted by the bijection
        v = np.asarray(valid)                     # [M, T, k]
        y_ref = np.zeros((M, T, d), np.float32)
        for m in range(M):
            contrib = (3.0 * xf[m][:, None, :] * gate_w[m][..., None]
                       * v[m][..., None]).sum(1)
            y_ref[(m + SHIFT) % M] = contrib + v[m][:, 0:1] * xf[m]
        np.testing.assert_allclose(np.asarray(y), y_ref,
                                   rtol=0, atol=1e-5)
        assert np.abs(y_ref).sum() > 0
        print("OK")
    """)
    assert "OK" in out


def test_condense_golden_grid_8dev():
    """Acceptance (ISSUE 5): on the 8-device hier mesh, (a) the "lsh"
    backend trains to a finite loss with measured_pairs strictly below
    "exact"; (b) hier_dedup="on" matches the flat wire within the
    documented tolerance with inter_bytes_shipped == inter_bytes_dedup
    and < inter_bytes_flat, and gradients flow; (c) condense-plan reuse
    under stable routing drops similarity builds to 1 per forward,
    bitwise-equal to condense_reuse="off" when the rebuild would emit
    the same rep map."""
    out = _run("""
        cfg = reduced(get_config("moe-gpt2"), num_layers=3, d_model=128)
        cfg = dataclasses.replace(cfg, compute_dtype="float32")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        shape = ShapeConfig("t", 64, 16, "train")
        data = SyntheticLM(cfg, shape)
        b = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
        cap = capacity_for(cfg.moe, 64, cfg.moe.num_experts, slack=8.0)
        mesh = make_mesh((2, 2, 2), ("data", "node", "local"))
        dist = DistContext(mesh, batch_axes=("data", "node", "local"),
                           seq_axis=None, fsdp_axes=("data",),
                           model_axis=("node", "local"),
                           topology=Topology(2, 2))

        def loss(params, luffy, thr=0.4):
            l, m = jax.jit(lambda p, bb: model.train_loss(
                p, bb, jnp.float32(thr), luffy=luffy, dist=dist,
                capacity=cap))(params, b)
            return float(l), {k: float(v) for k, v in m.items()}

        base = LuffyConfig(enable_condensation=True,
                           enable_migration=False, combine_slack=4.0,
                           condense_group=32, comm_mode="hier")

        # (a) LSH backend: finite loss, strictly fewer measured pairs
        le, me = loss(params, base)
        ll, ml = loss(params,
                      dataclasses.replace(base,
                                          similarity_backend="lsh"))
        assert np.isfinite(ll), ll
        assert 0 < ml["measured_pairs"] < me["measured_pairs"], (
            ml["measured_pairs"], me["measured_pairs"])

        # (b) dedup wire vs flat, with gradients
        flat = dataclasses.replace(base, comm_mode="flat")
        ded = dataclasses.replace(base, hier_dedup="on")
        lf, mf = loss(params, flat)
        ld, md = loss(params, ded)
        assert abs(lf - ld) < 2e-5, (lf, ld)
        assert md["inter_bytes_shipped"] == md["inter_bytes_dedup"]
        assert md["inter_bytes_shipped"] < md["inter_bytes_flat"]
        assert mf["inter_bytes_shipped"] == 0.0
        g = jax.jit(jax.grad(lambda p, bb: model.train_loss(
            p, bb, jnp.float32(0.4), luffy=ded, dist=dist,
            capacity=cap)[0]))(params, b)
        gn = float(sum(jnp.sum(jnp.abs(x))
                       for x in jax.tree.leaves(g)))
        assert np.isfinite(gn) and gn > 0, gn

        # (c) condense reuse. Stable routing = zeroed routers; at a
        # threshold above 1 the rebuild provably emits the identity rep
        # map every sublayer, so reuse is bitwise-equal to "off" while
        # the build counter drops 3 -> 1.
        stable = dict(params)
        stable["layers"] = [dict(params["layers"][0])]
        stable["layers"][0]["moe"] = dict(params["layers"][0]["moe"])
        stable["layers"][0]["moe"]["router"] = {
            "w_gate": jnp.zeros_like(
                params["layers"][0]["moe"]["router"]["w_gate"])}
        COUNTERS = ("condense_built", "condense_reused", "measured_pairs")
        off = dataclasses.replace(base, comm_mode="flat")
        sig = dataclasses.replace(off, condense_reuse="signature")
        l0, m0 = loss(stable, off, thr=1.5)
        l1, m1 = loss(stable, sig, thr=1.5)
        assert l0 == l1, (l0, l1)
        for k in m0:
            if k not in COUNTERS:
                assert m0[k] == m1[k], (k, m0[k], m1[k])
        assert m0["condense_built"] == 3.0
        assert m1["condense_built"] == 1.0, m1
        assert m1["condense_reused"] == 2.0
        assert m1["measured_pairs"] < m0["measured_pairs"]

        # realistic threshold: builds still drop to 1 per forward
        l2, m2 = loss(stable, sig, thr=0.4)
        assert np.isfinite(l2)
        assert m2["condense_built"] == 1.0, m2
        # drifting routing (per-layer routers): reuse never fires, and
        # signature mode stays bitwise-equal to off by graph parity
        l3, m3 = loss(params, off)
        l4, m4 = loss(params, sig)
        assert l3 == l4, (l3, l4)
        assert m4["condense_built"] == 3.0 and m4["condense_reused"] == 0.0
        # migrate + condense reuse: carries migrate with sequences
        mig = dataclasses.replace(sig, enable_migration=True)
        l5, m5 = loss(stable, mig, thr=1.5)
        assert np.isfinite(l5)
        assert m5["condense_built"] == 1.0, m5
        print("OK")
    """)
    assert "OK" in out
