"""repro.comm: topology descriptor, link-cost-weighted migration,
hierarchical two-phase collectives (subprocess, 8 host devices), and the
inter-node dedup traffic ledger (DESIGN.md §5)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.comm import (Topology, dispatch_bytes, expected_dedup_factor,
                        simulate_dispatch_rows)
from repro.core import migration as mig

ROOT = os.path.join(os.path.dirname(__file__), "..")


# ---------------------------------------------------------------------------
# topology
# ---------------------------------------------------------------------------

def test_topology_link_cost_matrix():
    t = Topology(num_nodes=2, devices_per_node=2, intra_bw=4e10,
                 inter_bw=1e10)
    c = t.link_cost()
    assert c.shape == (4, 4)
    assert np.allclose(np.diag(c), 0.0)
    assert c[0, 1] == 1.0 and c[2, 3] == 1.0          # intra-node
    assert c[0, 2] == 4.0 and c[1, 3] == 4.0          # bw_ratio across
    assert np.array_equal(c, c.T)
    assert t.bw_ratio == 4.0
    assert np.array_equal(np.asarray(t.node_of(np.arange(4))), [0, 0, 1, 1])


def test_flat_topology_degenerates_to_uniform():
    t = Topology.flat(4)
    assert not t.hierarchical
    c = t.link_cost()
    assert np.array_equal(c, np.ones((4, 4)) - np.eye(4))


# ---------------------------------------------------------------------------
# t_att host/device parity (cost-model normalization)
# ---------------------------------------------------------------------------

def test_t_att_parity_host_vs_traced():
    import jax.numpy as jnp
    want = (3.0 * 2 * 128 * 64 * 64 + 2.0 * 2 * 128 * 128 * 64) / 1e9
    host_scalar = mig.t_att(2, 128, 64, 1e9)
    host_np = mig.t_att(np.int64(2), np.int64(128), 64, 1e9)
    traced = mig.t_att(jnp.float32(2), jnp.float32(128), 64, 1e9)
    assert isinstance(host_scalar, float)             # no device round-trip
    assert isinstance(host_np, np.floating)
    assert abs(host_scalar - want) < 1e-9
    assert abs(float(host_np) - want) < 1e-9
    assert abs(float(traced) - want) / want < 1e-6    # f32 vs f64


# ---------------------------------------------------------------------------
# link-cost-weighted migration planning
# ---------------------------------------------------------------------------

def _instance(seed, n_slots, M):
    r = np.random.default_rng(seed)
    counts = (r.random((n_slots, M)) ** 3)
    counts = (counts / counts.sum(1, keepdims=True) * 100).astype(np.int64)
    counts = counts + r.random(counts.shape) * 1e-3   # break ties
    lens = r.integers(10, 100, n_slots).astype(np.int64)
    return counts.astype(np.float64), lens


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_plan_np_uniform_link_cost_matches_none(seed):
    """An explicit uniform matrix must reproduce the no-matrix plan."""
    counts, lens = _instance(seed, 8, 4)
    base = mig.plan_migration_np(counts, lens, 2, q=2)
    uni = mig.plan_migration_np(counts, lens, 2, q=2,
                                link_cost=np.ones((4, 4)) - np.eye(4))
    np.testing.assert_array_equal(np.asarray(base.assign),
                                  np.asarray(uni.assign))
    assert float(base.traffic_after) == float(uni.traffic_after)


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
def test_plan_np_jax_linkcost_parity(seed):
    """np and jax planners stay in lock-step under a hierarchical cost
    matrix, produce valid bijections, and never worsen weighted traffic."""
    topo = Topology(num_nodes=2, devices_per_node=2)
    cost = topo.link_cost()
    counts, lens = _instance(seed, 8, 4)
    p_np = mig.plan_migration_np(counts, lens, 2, q=2, link_cost=cost)
    p_jx = mig.plan_migration_jax(
        np.asarray(counts, np.float32), np.asarray(lens, np.float32), 2,
        q=2, link_cost=cost)
    np.testing.assert_array_equal(np.asarray(p_np.assign),
                                  np.asarray(p_jx.assign))
    np.testing.assert_array_equal(np.asarray(p_np.perm),
                                  np.asarray(p_jx.perm))
    perm = np.asarray(p_np.perm)
    assert sorted(perm.tolist()) == list(range(8))
    assert float(p_np.traffic_after) <= float(p_np.traffic_before) + 1e-6
    assert abs(float(p_np.traffic_after) - float(p_jx.traffic_after)) \
        < 1e-2 * max(1.0, float(p_np.traffic_after))


def test_plan_weighted_prefers_intra_node():
    """A slot pulled equally by an intra-node and an inter-node device
    must be homed on the cheap link."""
    topo = Topology(num_nodes=2, devices_per_node=2, intra_bw=8e10,
                    inter_bw=1e10)
    # slot 0 lives on device 0; devices 1 (same node) and 2 (other node)
    # each host 50 of its token copies.
    counts = np.zeros((4, 4)) + 1e-3
    counts[0, 1] = 50.0
    counts[0, 2] = 50.0
    lens = np.array([40, 30, 20, 10])
    plan = mig.plan_migration_np(counts, lens, 1, q=4,
                                 link_cost=topo.link_cost())
    # homed at 0 or 1 the copies on device 1 travel cheap links and only
    # device 2's cross nodes; homed at 2 or 3 the device-1 copies cross
    # too. With bw_ratio 8 the weighted greedy must stay on node 0 (the
    # unweighted objective is indifferent between devices 1 and 2).
    assert int(plan.assign[0]) in (0, 1)


# ---------------------------------------------------------------------------
# analytic dedup ledger
# ---------------------------------------------------------------------------

def test_expected_dedup_factor_bounds():
    topo = Topology(num_nodes=4, devices_per_node=4)
    assert expected_dedup_factor(1, topo) == 1.0
    f2 = expected_dedup_factor(2, topo)
    f4 = expected_dedup_factor(4, topo)
    assert 0.0 < f4 < f2 < 1.0
    flat = Topology.flat(16)
    assert expected_dedup_factor(4, flat) == 1.0


def test_dispatch_bytes_dedup_and_condensation_shrink_inter():
    topo = Topology(num_nodes=2, devices_per_node=4)
    _, inter_flat = dispatch_bytes(1024, 2, 64, topo=topo)
    _, inter_hier = dispatch_bytes(1024, 2, 64, topo=topo, dedup=True)
    _, inter_cond = dispatch_bytes(1024, 2, 64, topo=topo, dedup=True,
                                   r_cond=0.5)
    assert inter_hier < inter_flat
    assert inter_cond < inter_hier
    mc = np.random.default_rng(0)
    flat_r, dedup_r, _ = simulate_dispatch_rows(mc, 2048, 2, topo)
    # monte-carlo (distinct top-k draws) tracks the independent-draw
    # closed form to within a few percent
    assert abs(dedup_r / flat_r
               - expected_dedup_factor(2, topo)) < 0.06


def test_commsim_hier_variants():
    from repro.core import commsim
    from repro.configs import get_config
    cfg = get_config("moe-gpt2", num_experts=8)
    setup = commsim.PaperSetup(cfg=cfg)
    comp, comm = commsim.PAPER_VANILLA["moe-gpt2"][8]
    cal = commsim.calibrate(setup, comp, comm)
    van = commsim.predict(setup, cal, system="vanilla")
    vh = commsim.predict(setup, cal, system="vanilla-hier",
                         topo=commsim.default_topology(8, nodes=2,
                                                       bw_ratio=4.0))
    lh = commsim.predict(setup, cal, system="luffy-hier",
                         topo=commsim.default_topology(8, nodes=2,
                                                       bw_ratio=4.0))
    # hierarchical vanilla beats flat vanilla (dedup + cheap intra links)
    assert vh["comm_ms"] < van["comm_ms"]
    assert lh["comm_ms"] < vh["comm_ms"]              # + condensation
    assert vh["comp_ms"] == pytest.approx(van["comp_ms"])


# ---------------------------------------------------------------------------
# multi-device: hierarchical collectives + end-to-end comm_mode parity
# (subprocesses with 8 forced host devices, like test_multidevice.py)
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
        from repro.comm import (CommContext, Topology, hier_all_to_all,
                                make_mesh)
    """) + textwrap.dedent(script_body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", script], cwd=ROOT,
                         capture_output=True, text=True, env=env,
                         timeout=1200)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_hier_all_to_all_matches_flat_collective():
    out = _run("""
        N, L, R = 2, 4, 5
        M = N * L
        mesh = make_mesh((N, L), ("node", "local"))
        x = jnp.asarray(np.random.default_rng(0).standard_normal(
            (M * M, R)), jnp.float32)

        flat = shard_map(
            lambda b: jax.lax.all_to_all(b, ("node", "local"), split_axis=0,
                                         concat_axis=0, tiled=True),
            mesh=mesh, in_specs=P(("node", "local"), None),
            out_specs=P(("node", "local"), None))(x)
        hier = shard_map(
            lambda b: hier_all_to_all(b, "node", "local"),
            mesh=mesh, in_specs=P(("node", "local"), None),
            out_specs=P(("node", "local"), None))(x)
        assert np.array_equal(np.asarray(flat), np.asarray(hier))
        # involution: routing back restores the input exactly
        back = shard_map(
            lambda b: hier_all_to_all(b, "node", "local"),
            mesh=mesh, in_specs=P(("node", "local"), None),
            out_specs=P(("node", "local"), None))(hier)
        assert np.array_equal(np.asarray(back), np.asarray(x))
        print("OK")
    """)
    assert "OK" in out


def test_comm_mode_hier_bit_identical_and_dedups_inter_bytes():
    out = _run("""
        from repro.configs import get_config
        from repro.config import reduced, LuffyConfig, ShapeConfig
        from repro.models.model import build_model
        from repro.dist import DistContext
        from repro.data import SyntheticLM
        from repro.core.moe_layer import capacity_for

        cfg = reduced(get_config("moe-gpt2"), num_layers=2)
        cfg = dataclasses.replace(cfg, compute_dtype="float32")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        shape = ShapeConfig("t", 128, 8, "train")
        data = SyntheticLM(cfg, shape)
        b = {k: jnp.asarray(v) for k, v in data.batch(0).items()}

        mesh = make_mesh((2, 2, 2), ("data", "node", "local"))
        topo = Topology(num_nodes=2, devices_per_node=2)
        dist = DistContext(mesh, batch_axes=("data", "node", "local"),
                           seq_axis=None, fsdp_axes=("data",),
                           model_axis=("node", "local"), topology=topo)
        cap = capacity_for(cfg.moe, 128, cfg.moe.num_experts, slack=8.0)
        flat = LuffyConfig(enable_condensation=True, enable_migration=True,
                           combine_slack=4.0, condense_group=64,
                           comm_mode="flat")
        hier = dataclasses.replace(flat, comm_mode="hier")
        lf, mf = jax.jit(lambda p, bb: model.train_loss(
            p, bb, jnp.float32(0.4), luffy=flat, dist=dist,
            capacity=cap))(params, b)
        lh, mh = jax.jit(lambda p, bb: model.train_loss(
            p, bb, jnp.float32(0.4), luffy=hier, dist=dist,
            capacity=cap))(params, b)
        # bit-identical layer outputs -> bit-identical loss
        assert float(lf) == float(lh), (float(lf), float(lh))
        assert float(mh["condense_rate"]) > 0.0
        # the hier path ships strictly fewer inter-node dispatch bytes
        assert float(mh["inter_bytes_flat"]) > 0.0
        assert float(mh["inter_bytes_dedup"]) < float(mh["inter_bytes_flat"])
        # the flat path's ledger shows no dedup (ships every copy)
        assert float(mf["inter_bytes_dedup"]) == float(mf["inter_bytes_flat"])
        print("OK", float(lf),
              float(mh["inter_bytes_dedup"]) / float(mh["inter_bytes_flat"]))
    """)
    assert "OK" in out


def test_hier_mesh_vanilla_matches_single_device():
    """The hierarchical mesh + two-phase collectives reproduce the
    single-device forward (sanity against relabeling bugs)."""
    out = _run("""
        from repro.configs import get_config
        from repro.config import reduced, LuffyConfig, ShapeConfig
        from repro.models.model import build_model
        from repro.dist import DistContext, single_device
        from repro.data import SyntheticLM
        from repro.core.moe_layer import capacity_for

        cfg = reduced(get_config("moe-gpt2"), num_layers=2)
        cfg = dataclasses.replace(cfg, compute_dtype="float32")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        shape = ShapeConfig("t", 128, 8, "train")
        data = SyntheticLM(cfg, shape)
        b = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
        off = LuffyConfig(enable_condensation=False, enable_migration=False,
                          comm_mode="hier")
        cap1 = capacity_for(cfg.moe, 8 * 128, cfg.moe.num_experts, slack=8.0)
        cap8 = capacity_for(cfg.moe, 128, cfg.moe.num_experts, slack=8.0)
        l1, _ = model.train_loss(params, b, jnp.float32(1.0), luffy=off,
                                 dist=single_device(), capacity=cap1)
        mesh = make_mesh((2, 2, 2), ("data", "node", "local"))
        dist = DistContext(mesh, batch_axes=("data", "node", "local"),
                           seq_axis=None, fsdp_axes=("data",),
                           model_axis=("node", "local"),
                           topology=Topology(2, 2))
        l2, m2 = jax.jit(lambda p, bb: model.train_loss(
            p, bb, jnp.float32(1.0), luffy=off, dist=dist,
            capacity=cap8))(params, b)
        assert abs(float(l1) - float(l2)) < 5e-3, (float(l1), float(l2))
        assert float(m2["dispatch_drop"]) == 0.0
        print("OK", float(l1), float(l2))
    """)
    assert "OK" in out
