"""Low-precision wire (``LuffyConfig.wire_dtype``, DESIGN.md §14).

Pins the ISSUE-9 contracts:

* codec round-trip properties (bf16 exact on bf16-representable rows,
  f8e4m3 bounded relative error against the block scale);
* the single pricing source — ``estimate_exchange`` scales every
  modeled byte field by exactly ``1 / wire_precision``;
* serialization v3 (wire_dtype + scale-block in the header, v2 blobs
  rejected) and cache-key membership (a dtype change is a MISS);
* the executed 8-device contracts: the bf16 wire is bit-identical to a
  reference quantize-then-exchange path, the golden grid stays within
  tolerance of the f32 wire, and the executed ``inter_bytes_shipped``
  equals ``flat / (dedup × precision)`` exactly — since ISSUE 10 in
  EVERY execution mode (vanilla, migrate, pipelined: the dedup wire is
  universal, DESIGN.md §15);
* wire error feedback (``LuffyConfig.wire_error_feedback``): residual
  shape/zero/nonzero contracts and the carried-residual step's loss
  tolerance.
"""
import os
import struct
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st   # optional dep; skips when absent

from repro.comm import dtypes as wdt
from repro.config import LuffyConfig, ModelConfig, MoEConfig

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _mk(num_experts=4, top_k=2):
    return ModelConfig(
        name="t", kind="decoder", family="moe", num_layers=2,
        d_model=32, d_ff=64, vocab_size=128,
        moe=MoEConfig(num_experts=num_experts, top_k=top_k, d_ff=64,
                      num_shared_experts=1),
        layer_ffn_pattern=("moe",), compute_dtype="float32",
        param_dtype="float32")


# ------------------------------------------------------------ wire math

def test_wire_precision_identity_and_monotone():
    """f32 is the identity wire — row bytes reduce EXACTLY to the
    historical (d+2)·itemsize — and precision is monotone toward f8."""
    for d in (17, 32, 64, 128, 1000):
        for ce in (2, 4):
            assert wdt.wire_row_bytes(d, "f32", ce) == (d + 2) * ce
            p32 = wdt.wire_precision(d, "f32", ce)
            p16 = wdt.wire_precision(d, "bf16", ce)
            p8 = wdt.wire_precision(d, "f8e4m3", ce)
            assert p32 == 1.0
            assert 1.0 <= p16 <= p8
            # f8 sideband arithmetic: one f32 scale per 32 elements
            assert wdt.wire_row_bytes(d, "f8e4m3", ce) == \
                d + 4 * ((d + 31) // 32) + 2 * ce


def test_validate_wire_dtype():
    assert wdt.validate_wire_dtype("f32") == "f32"
    assert wdt.validate_wire_dtype("bf16") == "bf16"
    with pytest.raises(ValueError, match="wire_dtype"):
        wdt.validate_wire_dtype("fp4")
    if wdt.have_f8():
        assert wdt.validate_wire_dtype("f8e4m3") == "f8e4m3"


def test_estimate_prices_wire_exactly():
    """Single pricing source: every modeled byte field scales by exactly
    1/precision, and modeled step time is monotone non-increasing toward
    fp8 (dryrun ledger, commsim, objectives, autotune inherit free)."""
    from repro.comm.topology import Topology
    from repro.plan.estimate import estimate_exchange
    topo = Topology(2, 4)
    kw = dict(topo=topo, num_layers=2, ffn_ms=1.0)
    e32 = estimate_exchange(4096, 2, 128, **kw)
    e16 = estimate_exchange(4096, 2, 128, wire_dtype="bf16", **kw)
    prec = wdt.wire_precision(128, "bf16", 4)
    fields = ("inter_dispatch_bytes", "intra_dispatch_bytes",
              "flat_inter_dispatch_bytes", "flat_intra_dispatch_bytes")
    for f in fields:
        assert getattr(e16, f) == pytest.approx(getattr(e32, f) / prec)
    assert e16.sync_ms <= e32.sync_ms
    assert e16.dispatch_ms <= e32.dispatch_ms
    if wdt.have_f8():
        e8 = estimate_exchange(4096, 2, 128, wire_dtype="f8e4m3", **kw)
        p8 = wdt.wire_precision(128, "f8e4m3", 4)
        for f in fields:
            assert getattr(e8, f) == pytest.approx(
                getattr(e32, f) / p8)
        assert e8.sync_ms <= e16.sync_ms


# ------------------------------------------------------- codec round-trip

@settings(max_examples=40, deadline=None)
@given(st.data())
def test_quantize_roundtrip_property(data):
    """bf16 wire: exact on bf16-representable rows. f8e4m3 wire: per
    element |deq − x| ≤ blockmax/16 (half-ulp at the top of the e4m3
    range is blockmax/28), zero rows reconstruct exactly."""
    n = data.draw(st.integers(1, 8), label="rows")
    d = data.draw(st.integers(1, 70), label="d_model")
    mag = data.draw(st.sampled_from([1e-3, 1.0, 1e2]), label="magnitude")
    seed = data.draw(st.integers(0, 2**31 - 1), label="seed")
    r = np.random.default_rng(seed)
    x = jnp.asarray((r.standard_normal((n, d)) * mag).astype(np.float32))

    xb = x.astype(jnp.bfloat16).astype(jnp.float32)   # representable
    q, sc = wdt.quantize_rows(xb, "bf16")
    assert sc is None and q.dtype == jnp.bfloat16
    back = wdt.dequantize_rows(q, sc, jnp.float32, d)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(xb))

    if not wdt.have_f8():
        return
    q, sc = wdt.quantize_rows(x, "f8e4m3")
    d_pad = wdt.pad_to_block(d)
    assert q.shape == (n, d_pad)
    assert sc.shape == (n, d_pad // wdt.SCALE_BLOCK)
    back = np.asarray(wdt.dequantize_rows(q, sc, jnp.float32, d))
    assert back.shape == (n, d)
    xp = np.zeros((n, d_pad), np.float32)
    xp[:, :d] = np.asarray(x)
    amax = np.max(np.abs(xp.reshape(n, -1, wdt.SCALE_BLOCK)), axis=-1)
    bound = np.repeat(amax / 16.0, wdt.SCALE_BLOCK, axis=-1)[:, :d]
    assert np.all(np.abs(back - np.asarray(x)) <= bound + 1e-12)
    # all-zero rows reconstruct exactly (scale pinned to 1.0)
    z = jnp.zeros((2, d), jnp.float32)
    qz, sz = wdt.quantize_rows(z, "f8e4m3")
    assert np.all(np.asarray(sz) == 1.0)
    np.testing.assert_array_equal(
        np.asarray(wdt.dequantize_rows(qz, sz, jnp.float32, d)),
        np.asarray(z))


def test_quantize_roundtrip_deterministic():
    """Non-property twin of the hypothesis test (runs when the optional
    dep is absent): same bf16-exactness and f8 error-bound contracts on
    fixed shapes."""
    r = np.random.default_rng(7)
    for n, d, mag in ((4, 33, 1.0), (2, 64, 1e-3), (8, 70, 1e2)):
        x = jnp.asarray((r.standard_normal((n, d)) * mag)
                        .astype(np.float32))
        xb = x.astype(jnp.bfloat16).astype(jnp.float32)
        q, sc = wdt.quantize_rows(xb, "bf16")
        back = wdt.dequantize_rows(q, sc, jnp.float32, d)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(xb))
        if not wdt.have_f8():
            continue
        q, sc = wdt.quantize_rows(x, "f8e4m3")
        back = np.asarray(wdt.dequantize_rows(q, sc, jnp.float32, d))
        d_pad = wdt.pad_to_block(d)
        xp = np.zeros((n, d_pad), np.float32)
        xp[:, :d] = np.asarray(x)
        amax = np.max(np.abs(xp.reshape(n, -1, wdt.SCALE_BLOCK)), -1)
        bound = np.repeat(amax / 16.0, wdt.SCALE_BLOCK, axis=-1)[:, :d]
        assert np.all(np.abs(back - np.asarray(x)) <= bound + 1e-12)


# ------------------------------------------------- serial v3 + cache key

def test_serial_v3_roundtrips_wire_dtype_and_rejects_v2():
    from repro.plan import (PlanFormatError, build_plan_template,
                            from_bytes, to_bytes)
    cfg = _mk()
    luffy = LuffyConfig(enable_condensation=False, enable_migration=False,
                        wire_dtype="bf16")
    tmpl = build_plan_template(cfg, luffy, n_seq=2, seq_len=16,
                               capacity=64)
    assert tmpl.wire_dtype == "bf16"
    plan2 = from_bytes(to_bytes(tmpl))
    assert plan2.wire_dtype == "bf16"
    # patch the u16 format-version field to 2: rejected, never misread
    data = bytearray(to_bytes(tmpl))
    v2 = bytes(data[:4]) + struct.pack("<H", 2) + bytes(data[6:])
    with pytest.raises(PlanFormatError, match="version 2"):
        from_bytes(v2)


def test_serial_rejects_foreign_scale_block(monkeypatch):
    """A reader must never decode f8 scales computed at a different
    block size — the header pins SCALE_BLOCK."""
    from repro.plan import PlanFormatError, build_plan_template, \
        from_bytes, to_bytes
    cfg = _mk()
    luffy = LuffyConfig(enable_condensation=False, enable_migration=False)
    data = to_bytes(build_plan_template(cfg, luffy, n_seq=2, seq_len=16,
                                        capacity=64))
    monkeypatch.setattr("repro.comm.dtypes.SCALE_BLOCK", 64)
    with pytest.raises(PlanFormatError, match="scale block"):
        from_bytes(data)


def test_plan_key_and_decode_key_miss_on_wire_dtype():
    from repro.plan import plan_key
    base = dict(n_seq=2, seq_len=16, d_model=32, capacity=64, top_k=2,
                num_experts=4, mode="vanilla", objective="traffic",
                exec_mode="sync", pipeline_chunks=1, comm_mode="local",
                topo=None, M=1)
    k32 = plan_key(**base)
    assert plan_key(**base, wire_dtype="f32") == k32   # default: no-op
    k16 = plan_key(**base, wire_dtype="bf16")
    assert k16 != k32
    assert "wdbf16" in k16
    # the serving keys thread LuffyConfig.wire_dtype through
    from repro.dist import single_device
    from repro.plan.cache import decode_plan_key, prefill_plan_key
    cfg = _mk()
    dist = single_device()
    lf = LuffyConfig(enable_condensation=False, enable_migration=False)
    lb = LuffyConfig(enable_condensation=False, enable_migration=False,
                     wire_dtype="bf16")
    assert decode_plan_key(cfg, lf, dist, 4) != \
        decode_plan_key(cfg, lb, dist, 4)
    assert prefill_plan_key(cfg, lf, dist, 2, 16) != \
        prefill_plan_key(cfg, lb, dist, 2, 16)


def test_build_plan_rejects_unknown_wire_dtype():
    from repro.plan import build_plan_template
    cfg = _mk()
    luffy = LuffyConfig(enable_condensation=False, enable_migration=False,
                        wire_dtype="fp4")
    with pytest.raises(ValueError, match="wire_dtype"):
        build_plan_template(cfg, luffy, n_seq=2, seq_len=16, capacity=64)


# ------------------------------------------------- 8-device (subprocess)

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
        from repro.comm import dtypes as wdt
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


def test_wire_dtype_dedup_bit_identity_8dev():
    """Executed bf16 wire == reference quantize-then-exchange: the wire
    quantizes immediately before the node-crossing collective, and a
    cast/quantize commutes with row permutation — so dispatch rows must
    be BIT-identical to dequantize(quantize(dense-wire rows)). Also pins
    the fused-kernel path (use_kernel=True) bitwise against the jnp
    fallback, for every supported wire dtype."""
    out = _run("""
        from repro.condense.wire import dedup_dispatch
        from repro.core.gating import dispatch_positions

        N, L = 2, 4
        M = N * L
        mesh = make_mesh((N, L), ("node", "local"))
        topo = Topology(N, L)
        comm = CommContext.build("hier", ("node", "local"), topo)
        T, k, d, E_local, C = 48, 2, 64, 2, 24
        E = E_local * M
        r = np.random.default_rng(0)
        xf = r.standard_normal((M, T, d)).astype(np.float32)
        expert_idx = r.integers(0, E, (M, T, k)).astype(np.int32)
        gate_w = r.random((M, T, k)).astype(np.float32)
        wds = ["f32", "bf16"] + (["f8e4m3"] if wdt.have_f8() else [])

        def inner(xf_l, e_l, g_l):
            xf_l, e_l, g_l = xf_l[0], e_l[0], g_l[0]
            keep = jnp.ones((T, k), bool)
            pos = dispatch_positions(e_l, keep, E)
            valid = keep & (pos < C)
            # dense f32 reference rows through the dense wire
            pay = jnp.concatenate([
                jnp.tile(xf_l[:, None], (1, k, 1)),
                g_l[..., None]], -1).reshape(-1, d + 1)
            v_f = valid.reshape(-1)
            e_s = jnp.where(v_f, e_l.reshape(-1), 0)
            p_s = jnp.where(v_f, pos.reshape(-1), 0)
            buf = jnp.zeros((E, C, d + 1), jnp.float32).at[e_s, p_s].add(
                pay * v_f[:, None], mode="drop")
            buf = comm.all_to_all(buf)
            rows = buf.reshape(M, E_local, C, d + 1) \
                      .transpose(1, 0, 2, 3)[..., :d]
            outs = []
            for wd in wds:
                xr, gw, rv, st = dedup_dispatch(
                    xf_l, e_l, g_l, valid, pos, comm=comm,
                    e_local=E_local, capacity=C, wire_dtype=wd)
                xk, gk, _, _ = dedup_dispatch(
                    xf_l, e_l, g_l, valid, pos, comm=comm,
                    e_local=E_local, capacity=C, wire_dtype=wd,
                    use_kernel=True)
                # reference: quantize-then-exchange == exchange-then-
                # quantize for a row permutation
                q, sc = wdt.quantize_rows(rows, wd)
                want = wdt.dequantize_rows(q, sc, jnp.float32, d)
                outs += [xr, xk, want, gw, gk]
            return tuple(jnp.asarray(a)[None] for a in outs)

        fn = shard_map(inner, mesh=mesh,
                       in_specs=(P(("node", "local")),) * 3,
                       out_specs=(P(("node", "local")),) * (5 * len(wds)))
        res = fn(jnp.asarray(xf), jnp.asarray(expert_idx),
                 jnp.asarray(gate_w))
        for i, wd in enumerate(wds):
            xr, xk, want, gw, gk = res[5 * i:5 * i + 5]
            assert np.array_equal(np.asarray(xr), np.asarray(want)), (
                "wire rows not bit-identical to quantize-then-exchange "
                f"reference ({wd})")
            assert np.array_equal(np.asarray(xk), np.asarray(xr)), (
                f"fused kernel path diverges from fallback ({wd})")
            assert np.array_equal(np.asarray(gk), np.asarray(gw)), (
                f"gate rows must never quantize ({wd})")
        print("OK")
    """)
    assert "OK" in out


def test_wire_dtype_golden_grid_8dev():
    """Acceptance (ISSUE 9 + 10): on the 8-device hier mesh, the bf16
    wire trains within tolerance of f32 across {vanilla, migrate} ×
    {flat, hier} × {dedup on/off} — now ALSO the pipelined exec mode —
    gradients stay finite, and with the dedup wire on, the executed
    ``inter_bytes_shipped`` equals the modeled flat / (dedup ×
    precision) exactly IN EVERY MODE (the wire is universal, DESIGN.md
    §15: dedup never ships zero when on). fp8 (when available) is
    looser: finite loss within the documented wide tolerance."""
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

        def loss(luffy):
            l, m = jax.jit(lambda p, bb: model.train_loss(
                p, bb, jnp.float32(0.4), luffy=luffy, dist=dist,
                capacity=cap))(params, b)
            return float(l), {k: float(v) for k, v in m.items()}

        d, ce = cfg.d_model, 4            # float32 compute
        combos = [(mig, cm, dd, "sync", 1)
                  for mig in (False, True)
                  for cm, dd in (("flat", "off"), ("hier", "off"),
                                 ("hier", "on"))]
        # ISSUE 10: the chunked dedup wire under the pipelined exchange
        combos += [(False, "hier", "on", "pipeline", 2),
                   (True, "hier", "on", "pipeline", 2)]
        for migrate, comm_mode, dedup, em, nc in combos:
            base = LuffyConfig(
                enable_condensation=True, enable_migration=migrate,
                combine_slack=4.0, condense_group=32,
                comm_mode=comm_mode, hier_dedup=dedup,
                exec_mode=em, pipeline_chunks=nc)
            l32, m32 = loss(base)
            l16, m16 = loss(dataclasses.replace(base,
                                                wire_dtype="bf16"))
            tag = (migrate, comm_mode, dedup, em)
            assert np.isfinite(l16), tag
            assert abs(l16 - l32) < 0.05, (tag, l32, l16)
            # universal-wire contract: dedup on => bytes actually ship
            # through the dedup wire, in every (mode, exec) combination
            if dedup == "on":
                assert m16["inter_bytes_shipped"] > 0, tag
            # exact executed-bytes ledger contract: shipped ==
            # dedup_bytes/precision == flat/(dedup x precision)
            if m16["inter_bytes_shipped"] > 0:
                prec = wdt.wire_precision(d, "bf16", ce)
                rows = m16["inter_bytes_dedup"] / ((d + 2) * ce)
                want = rows * wdt.wire_row_bytes(d, "bf16", ce)
                # exact up to the f32 metric accumulator: the only
                # slack is re-deriving rows from an averaged f32
                assert np.isclose(m16["inter_bytes_shipped"], want,
                                  rtol=1e-6, atol=0.0), (
                    tag, m16["inter_bytes_shipped"], want)
                assert abs(m16["inter_bytes_shipped"]
                           - m16["inter_bytes_dedup"] / prec) < 0.5
                assert m16["inter_bytes_shipped"] < \
                    m16["inter_bytes_flat"]
            else:
                assert dedup == "off", tag

        # gradients flow through the quantized wire
        ded16 = LuffyConfig(enable_condensation=True,
                            enable_migration=False, combine_slack=4.0,
                            condense_group=32, comm_mode="hier",
                            hier_dedup="on", wire_dtype="bf16")
        g = jax.jit(jax.grad(lambda p, bb: model.train_loss(
            p, bb, jnp.float32(0.4), luffy=ded16, dist=dist,
            capacity=cap)[0]))(params, b)
        gn = float(sum(jnp.sum(jnp.abs(x)) for x in jax.tree.leaves(g)))
        assert np.isfinite(gn) and gn > 0, gn

        # fp8: documented looser contract — finite, same ballpark
        if wdt.have_f8():
            l32, _ = loss(LuffyConfig(enable_condensation=True,
                                      enable_migration=False,
                                      combine_slack=4.0,
                                      condense_group=32,
                                      comm_mode="hier",
                                      hier_dedup="on"))
            l8, m8 = loss(LuffyConfig(enable_condensation=True,
                                      enable_migration=False,
                                      combine_slack=4.0,
                                      condense_group=32,
                                      comm_mode="hier", hier_dedup="on",
                                      wire_dtype="f8e4m3"))
            assert np.isfinite(l8), l8
            assert abs(l8 - l32) < 0.5, (l32, l8)
            rows = m8["inter_bytes_dedup"] / ((d + 2) * ce)
            want = rows * wdt.wire_row_bytes(d, "f8e4m3", ce)
            assert np.isclose(m8["inter_bytes_shipped"], want,
                              rtol=1e-6, atol=0.0), (
                m8["inter_bytes_shipped"], want)
        print("OK")
    """)
    assert "OK" in out


def test_wire_error_feedback_8dev():
    """Satellite (ISSUE 10): ``LuffyConfig.wire_error_feedback`` — the
    per-token wire quantization residual ``x − deq(quant(x))`` comes
    back per (layer, slot, position) under ``metrics["_wire_ef"]``, is
    identically zero under the exact f32 wire, nonzero under a lossy
    one, and a step fed the carried residual stays within the golden-
    grid loss tolerance of the f32 baseline (vanilla AND migrate)."""
    out = _run("""
        from repro.models import transformer as tfm
        cfg = reduced(get_config("moe-gpt2"), num_layers=3, d_model=128)
        cfg = dataclasses.replace(cfg, compute_dtype="float32")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        B, S = 16, 64
        shape = ShapeConfig("t", S, B, "train")
        data = SyntheticLM(cfg, shape)
        b = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
        cap = capacity_for(cfg.moe, 64, cfg.moe.num_experts, slack=8.0)
        mesh = make_mesh((2, 2, 2), ("data", "node", "local"))
        dist = DistContext(mesh, batch_axes=("data", "node", "local"),
                           seq_axis=None, fsdp_axes=("data",),
                           model_axis=("node", "local"),
                           topology=Topology(2, 2))

        def loss(luffy, ef):
            l, m = jax.jit(lambda p, bb, e: model.train_loss(
                p, bb, jnp.float32(0.4), luffy=luffy, dist=dist,
                capacity=cap, wire_ef=e))(params, b, ef)
            return float(l), m

        efs = tfm.wire_ef_shape(cfg, B, S)
        ef0 = jnp.zeros(efs, jnp.float32)
        for migrate in (False, True):
            base = LuffyConfig(enable_condensation=True,
                               enable_migration=migrate,
                               combine_slack=4.0, condense_group=32,
                               comm_mode="hier", hier_dedup="on",
                               wire_error_feedback=True)
            l32, m32 = loss(base, ef0)
            # exact f32 wire: the residual is identically zero
            z = np.asarray(m32["_wire_ef"])
            assert z.shape == efs and not z.any(), (migrate, z.shape)
            lq = dataclasses.replace(base, wire_dtype="bf16")
            l1, m1 = loss(lq, ef0)
            ef1 = m1["_wire_ef"]
            e1 = np.asarray(ef1)
            assert e1.shape == efs, (migrate, e1.shape)
            assert np.isfinite(e1).all() and np.abs(e1).max() > 0, migrate
            # step 2 eats the carried residual: still within tolerance
            l2, m2 = loss(lq, ef1)
            assert np.isfinite(l2), (migrate, l2)
            assert abs(l2 - l32) < 0.05, (migrate, l32, l2)
        print("OK")
    """)
    assert "OK" in out
