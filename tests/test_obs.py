"""repro.obs (DESIGN.md §11): span nesting + exclusive-time invariants
(property test), Chrome-trace export validity, the phase() hook (no-op
without a tracer, a profiler annotation with one, a named scope inside a
jax trace that names every layer of the compiled train step), the unified
metrics registry (canonical names, counter accumulation, applicability
masking — the inter_bytes_shipped null fix), the train step's packed
metrics (one device-to-host pull, bit for bit the per-key values),
calibration artifact round-trip + stale-fingerprint/version-drift miss
semantics, the plan_key chunk-overhead extension's backward
compatibility, and the 8-device traced-exchange invariant (subprocess)."""
import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

from _hyp import given, settings, st   # optional dep; skips when absent

from repro.config import LuffyConfig
from repro.obs import calibrate as obs_cal
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.calibrate import Calibration, calibration_key
from repro.obs.metrics import (COMM_LEDGER_SCHEMA_VERSION,
                               METRICS_SCHEMA_VERSION, MetricsRegistry,
                               canonical_name, flatten, mask_inapplicable)
from repro.obs.trace import NULL_SPAN, Tracer

ROOT = os.path.join(os.path.dirname(__file__), "..")


# ---------------------------------------------------------------------------
# trace: spans
# ---------------------------------------------------------------------------

def test_span_nesting_and_exclusive_time():
    tr = Tracer()
    with tr.span("outer"):
        with tr.span("a"):
            time.sleep(0.002)
        with tr.span("b"):
            time.sleep(0.002)
    ev = {e["name"]: e for e in tr.spans()}
    assert set(ev) == {"outer", "a", "b"}
    # children complete (and record) before the parent
    names = [e["name"] for e in tr.spans()]
    assert names.index("outer") > names.index("a")
    assert names.index("outer") > names.index("b")
    # inclusive parent time covers both children; exclusive excludes them
    child_dur = ev["a"]["dur"] + ev["b"]["dur"]
    assert ev["outer"]["dur"] >= child_dur
    assert ev["outer"]["args"]["self_us"] == pytest.approx(
        ev["outer"]["dur"] - child_dur, abs=1e-3)
    for e in tr.spans():
        assert 0.0 <= e["args"]["self_us"] <= e["dur"] + 1e-9


def _tree_strategy():
    return st.recursive(st.just([]),
                        lambda kids: st.lists(kids, max_size=3),
                        max_leaves=12)


@settings(max_examples=25, deadline=None)
@given(tree=_tree_strategy())
def test_span_tree_property(tree):
    """For ANY nesting structure: one event per span, post-order
    completion, child intervals contained in the parent's, and parent
    inclusive duration >= sum of direct-child durations."""
    tr = Tracer()
    parent_of = {}
    counter = [0]

    def walk(kids, parent_name):
        name = f"n{counter[0]}"
        counter[0] += 1
        parent_of[name] = parent_name
        with tr.span(name):
            for k in kids:
                walk(k, name)

    walk(tree, None)
    events = {e["name"]: e for e in tr.spans()}
    assert len(events) == len(parent_of)
    order = [e["name"] for e in tr.spans()]
    for name, parent in parent_of.items():
        if parent is None:
            continue
        c, p = events[name], events[parent]
        assert order.index(name) < order.index(parent)   # post-order
        assert c["ts"] >= p["ts"] - 1e-6
        assert c["ts"] + c["dur"] <= p["ts"] + p["dur"] + 1e-6
    for parent in parent_of.values():
        if parent is None:
            continue
        kids = [events[n] for n, p in parent_of.items() if p == parent]
        assert events[parent]["dur"] >= \
            sum(k["dur"] for k in kids) - 1e-6
        assert events[parent]["args"]["self_us"] == pytest.approx(
            events[parent]["dur"] - sum(k["dur"] for k in kids),
            abs=1e-3)


def test_chrome_trace_export(tmp_path):
    tr = Tracer()
    with tr.span("step", cat="step", step=0):
        pass
    path = tmp_path / "sub" / "trace.json"
    tr.write(path)
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
    for e in doc["traceEvents"]:
        assert {"name", "ph", "ts", "pid", "tid"} <= set(e)
        if e["ph"] == "X":                       # complete events
            assert "dur" in e and e["dur"] >= 0.0
    steps = [e for e in doc["traceEvents"] if e["name"] == "step"]
    assert steps[0]["args"]["step"] == 0


def test_chrome_trace_per_device_rows(tmp_path):
    """Device-tagged spans (the per-device exchange probe) get their own
    synthetic tid row plus a thread_name metadata event, so Perfetto
    shows devices side-by-side instead of flattening them onto the host
    thread."""
    from repro.obs.trace import DEVICE_TID_BASE
    tr = Tracer()
    for dev in range(3):
        with tr.span("probe_exchange", cat="probe", device=dev):
            pass
    with tr.span("step", cat="step"):          # untagged: host row
        pass
    doc = tr.to_chrome()
    probes = [e for e in doc["traceEvents"]
              if e["ph"] == "X" and e["name"] == "probe_exchange"]
    assert sorted(e["tid"] for e in probes) == [
        DEVICE_TID_BASE, DEVICE_TID_BASE + 1, DEVICE_TID_BASE + 2]
    (step,) = [e for e in doc["traceEvents"]
               if e["ph"] == "X" and e["name"] == "step"]
    assert step["tid"] < DEVICE_TID_BASE       # host tids are 16-bit
    names = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert {e["args"]["name"] for e in names} == \
        {"device 0", "device 1", "device 2"}
    # metadata events still satisfy the validity invariant above
    for e in names:
        assert {"name", "ph", "ts", "pid", "tid"} <= set(e)
    # the in-memory recorder is untouched: summary() still aggregates
    assert tr.summary()["probe_exchange"]["count"] == 3


def test_phase_hook_noop_without_tracer():
    obs_trace.deactivate()
    assert obs_trace.phase("dispatch") is NULL_SPAN
    sentinel = object()
    with obs_trace.phase("dispatch") as sp:
        assert sp.fence(sentinel) is sentinel
    tr = obs_trace.activate(Tracer())
    try:
        with obs_trace.phase("dispatch", cat="phase", layer=3):
            pass
    finally:
        obs_trace.deactivate()
    (e,) = tr.spans("dispatch")
    assert e["args"]["layer"] == 3


def test_phase_hook_noop_inside_jax_trace():
    """Inside a scan/jit body host timestamps are compile-time garbage:
    phase() records no host span there, and names the traced operations
    with a named scope instead."""
    import jax
    import jax.numpy as jnp
    tr = obs_trace.activate(Tracer())
    try:
        def body(c, x):
            with obs_trace.phase("inner") as sp:
                c = sp.fence(c + x)
            return c, c
        jax.lax.scan(body, jnp.float32(0.0), jnp.arange(4, dtype=jnp.float32))

        def jitted(x):
            with obs_trace.phase("jitted"):
                return jnp.sin(x)
        lowered = jax.jit(jitted).lower(jnp.float32(1.0))
    finally:
        obs_trace.deactivate()
    assert tr.spans("inner") == []
    assert tr.spans("jitted") == []
    assert 'op_name="jit(jitted)/jitted/sin"' in \
        lowered.compiler_ir("hlo").as_hlo_module().to_string()


def _profiled_host_events(log_dir):
    import glob
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    pd = ProfileData.from_file(path)
    return {ev.name for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}


def test_phase_records_a_host_span_and_annotation_only_with_a_tracer(
        tmp_path):
    """Outside a jax trace, phase() is NULL_SPAN without a tracer; with
    one it records a host span and a profiler TraceAnnotation of the same
    name, which puts the span on the device trace's clock."""
    import jax
    obs_trace.deactivate()
    with jax.profiler.trace(str(tmp_path)):
        assert obs_trace.phase("untraced_phase") is NULL_SPAN
        with obs_trace.phase("untraced_phase"):
            pass
        tr = obs_trace.activate(Tracer())
        try:
            with obs_trace.phase("traced_phase"):
                pass
        finally:
            obs_trace.deactivate()
    assert [e["name"] for e in tr.spans()] == ["traced_phase"]
    names = _profiled_host_events(tmp_path)
    assert "traced_phase" in names
    assert "untraced_phase" not in names


SCOPES_FWD_ONLY = ("plan_build", "condense", "optimizer")
SCOPES_BOTH = ("router", "dispatch", "expert_ffn", "combine", "attention",
               "embed", "lm_head")


def test_train_step_names_every_layer_scope():
    """The compiled train step of a tiny expert-parallel moe-gpt2 (4 host
    devices, migration and condensation on) carries each layer's scope in
    its instructions' op_name, in the forward pass and, where a gradient
    flows, in the backward pass (transpose(jvp(...))). The plan's
    decisions have no gradient and the optimizer runs on gradients."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import json, re
        import jax
        from repro import optim, train_lib
        from repro.comm import make_mesh
        from repro.config import (LuffyConfig, OptimConfig, ShapeConfig,
                                  reduced)
        from repro.configs import get_config
        from repro.dist import make_dist
        from repro.launch.mesh import topology_for_mesh
        from repro.models.model import build_model

        cfg = reduced(get_config("moe-gpt2"), num_layers=2, d_model=64,
                      max_experts=4, seq_len_hint=64)
        shape = ShapeConfig("train", 64, 4, "train")
        mesh = make_mesh((1, 4), ("data", "model"))
        dist = make_dist(mesh, "train", 4, moe_arch=True,
                         topology=topology_for_mesh(mesh))
        luffy = LuffyConfig(enable_condensation=True, enable_migration=True,
                            condense_group=32, combine_slack=2.0)
        ocfg = OptimConfig()
        model = build_model(cfg)
        struct = model.init_struct()
        cap = train_lib.capacity_for_bucket(cfg, shape, dist, luffy, 0)
        step = train_lib.make_train_step(
            cfg, luffy, ocfg, dist, cap,
            param_pspecs=model.param_pspecs(dist, struct))
        opt = jax.eval_shape(lambda p: optim.init_opt_state(p, ocfg), struct)
        lst = jax.eval_shape(train_lib.init_luffy_state)
        batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                 for k, v in model.input_specs(shape, dist).items()}
        text = jax.jit(step).lower(struct, opt, lst, batch).compile().as_text()
        found = {}
        for op in re.findall(r'op_name="([^"]*)"', text):
            for w in set(re.findall(r"\\w+", op)):
                found.setdefault(w, set()).add("bwd" if "transpose(" in op
                                               else "fwd")
        print(json.dumps({k: sorted(v) for k, v in found.items()}))
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", script], cwd=ROOT,
                         capture_output=True, text=True, env=env,
                         timeout=1200)
    assert out.returncode == 0, out.stderr[-3000:]
    found = json.loads(out.stdout.strip().splitlines()[-1])
    for scope in SCOPES_BOTH:
        assert found.get(scope) == ["bwd", "fwd"], (scope, found.get(scope))
    for scope in SCOPES_FWD_ONLY:
        assert found.get(scope) == ["fwd"], (scope, found.get(scope))


def test_tracer_summary():
    tr = Tracer()
    for _ in range(3):
        with tr.span("step"):
            with tr.span("io"):
                pass
    s = tr.summary()
    assert s["step"]["count"] == 3 and s["io"]["count"] == 3
    assert s["step"]["self_us"] <= s["step"]["total_us"]


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_canonical_names():
    assert canonical_name("loss") == "train/loss"
    assert canonical_name("plans_built") == "plan/built"
    assert canonical_name("inter_bytes_shipped") == \
        "comm/inter_bytes_shipped"
    assert canonical_name("reuse_mismatch") == "plan/reuse_mismatch"
    assert canonical_name("not_a_known_key") == "not_a_known_key"


def test_registry_counters_accumulate_gauges_dont():
    luffy = LuffyConfig(comm_mode="hier", hier_dedup="on")
    reg = MetricsRegistry(luffy=luffy, run_info={"arch": "x"})
    r0 = reg.observe(0, {"loss": 2.0, "plans_built": 2,
                         "inter_bytes_shipped": 100.0})
    r1 = reg.observe(1, {"loss": 1.0, "plans_built": 1,
                         "inter_bytes_shipped": 50.0})
    assert r0["schema_version"] == METRICS_SCHEMA_VERSION
    assert "run" in r0 and "run" not in r1          # stamped once
    assert r1["metrics"]["train/loss"] == 1.0
    assert r1["cumulative"]["plan/built"] == 3.0
    assert r1["cumulative"]["comm/inter_bytes_shipped"] == 150.0
    assert "train/loss" not in r1["cumulative"]     # gauges don't sum


def test_applicability_masking():
    raw = {"inter_bytes_flat": 10.0, "inter_bytes_dedup": 8.0,
           "inter_bytes_shipped": 0.0, "loss": 1.0}
    flat = mask_inapplicable(raw, LuffyConfig(comm_mode="flat"))
    assert flat["inter_bytes_flat"] is None
    assert flat["inter_bytes_shipped"] is None
    assert flat["loss"] == 1.0
    hier = mask_inapplicable(raw, LuffyConfig(comm_mode="hier"))
    assert hier["inter_bytes_flat"] == 10.0
    assert hier["inter_bytes_shipped"] is None      # dense wire: null
    dedup = mask_inapplicable(
        raw, LuffyConfig(comm_mode="hier", hier_dedup="on"))
    assert dedup["inter_bytes_shipped"] == 0.0
    # the registry reports the same nulls under canonical names and
    # never accumulates an inapplicable counter
    reg = MetricsRegistry(luffy=LuffyConfig(comm_mode="flat"))
    rec = reg.observe(0, raw)
    assert rec["metrics"]["comm/inter_bytes_flat"] is None
    assert "comm/inter_bytes_flat" not in rec["cumulative"]


def test_write_jsonl_appends(tmp_path):
    path = tmp_path / "deep" / "m.jsonl"
    obs_metrics.write_jsonl(path, {"step": 0})
    obs_metrics.write_jsonl(path, {"step": 1})
    recs = [json.loads(x) for x in path.read_text().splitlines()]
    assert [r["step"] for r in recs] == [0, 1]


def test_read_jsonl_tolerates_truncation(tmp_path):
    """A killed run leaves a valid JSONL prefix: every whole line (one
    atomic write each) parses, and a torn final line is skipped instead
    of poisoning the whole file."""
    path = tmp_path / "m.jsonl"
    for i in range(5):
        obs_metrics.write_jsonl(path, {"step": i, "metrics": {"x": i}})
    data = path.read_bytes()
    assert len(obs_metrics.read_jsonl(path)) == 5
    # chop the file mid-way through the last record (simulated kill)
    path.write_bytes(data[:-7])
    recs = obs_metrics.read_jsonl(path)
    assert [r["step"] for r in recs] == [0, 1, 2, 3]
    # record-by-record: every complete prefix parses at every cut point
    for cut in range(len(data)):
        path.write_bytes(data[:cut])
        recs = obs_metrics.read_jsonl(path)
        assert [r["step"] for r in recs] == list(range(len(recs)))
        assert len(recs) >= data[:cut].count(b"\n") - 1
    assert obs_metrics.read_jsonl(tmp_path / "absent.jsonl") == []


def test_flatten_nested():
    flat = flatten("comm_ledger", {"buckets": {"0.0": {"flat": 1}},
                                   "dedup_factor": 2.0})
    assert flat == {"comm_ledger/buckets/0.0/flat": 1,
                    "comm_ledger/dedup_factor": 2.0}


# ---------------------------------------------------------------------------
# calibration artifact
# ---------------------------------------------------------------------------

def _calib(key: str) -> Calibration:
    return Calibration(key=key, intra_bw=2e10, inter_bw=5e9,
                       intra_lat=1e-5, inter_lat=4e-5,
                       chunk_overhead_ms=0.07, plan_step_us=3.0,
                       sim_speed=1e11, ffn_speed=2e12,
                       samples={"rows_list": [64]})


def test_calibration_roundtrip():
    c = _calib("2x2i1e+10e2e+09l0-0__cpu")
    back = Calibration.from_json(c.to_json(), expect_key=c.key)
    assert back == c


def test_calibration_miss_semantics():
    c = _calib("2x2i1e+10e2e+09l0-0__cpu")
    text = c.to_json()
    # stale fingerprint (different topology/backend) is a MISS, not an
    # error and never a silent hit
    assert Calibration.from_json(text, expect_key="4x2i1e+10e2e+09l0-0"
                                 "__cpu") is None
    assert Calibration.from_json("{not json", expect_key=c.key) is None
    assert Calibration.from_json(json.dumps({"a": 1})) is None
    bumped = json.loads(text)
    bumped["schema_version"] = obs_cal.CALIBRATION_SCHEMA_VERSION + 1
    assert Calibration.from_json(json.dumps(bumped),
                                 expect_key=c.key) is None
    wrong_magic = json.loads(text)
    wrong_magic["magic"] = "something-else"
    assert Calibration.from_json(json.dumps(wrong_magic)) is None


def test_calibration_save_load_dir(tmp_path):
    c = _calib("flat4__cpu")
    path = obs_cal.save_calibration(tmp_path, c)
    assert path.name == "flat4__cpu.calib.json"
    assert obs_cal.load_calibration(tmp_path, c.key) == c
    assert obs_cal.load_calibration(tmp_path, "flat8__cpu") is None
    # a corrupted artifact is a miss too
    path.write_text(path.read_text().replace(obs_cal.CALIBRATION_MAGIC,
                                             "nope"))
    assert obs_cal.load_calibration(tmp_path, c.key) is None


def test_calibration_key_binds_backend_and_topology():
    from repro.comm.topology import Topology
    topo = Topology(2, 2, intra_bw=1e10, inter_bw=2e9)
    k_cpu = calibration_key(topo, 4, backend="cpu")
    k_tpu = calibration_key(topo, 4, backend="tpu")
    assert k_cpu.endswith("__cpu") and k_tpu.endswith("__tpu")
    assert k_cpu.split("__")[0] == k_tpu.split("__")[0]
    assert calibration_key(None, 4, backend="cpu") == "flat4__cpu"


def test_calibration_pricing_handoff():
    from repro.comm.topology import Topology
    c = _calib("2x2i1e+10e2e+09l0-0__cpu")
    topo = c.topology(Topology(2, 2, intra_bw=1e10, inter_bw=2e9))
    assert topo.intra_bw == c.intra_bw and topo.inter_bw == c.inter_bw
    assert topo.num_nodes == 2 and topo.devices_per_node == 2
    luffy = c.apply(LuffyConfig())
    assert luffy.gpu_speed == c.ffn_speed
    assert luffy.chunk_overhead_ms == c.chunk_overhead_ms
    kw = c.estimate_kwargs()
    assert set(kw) == {"intra_bw", "inter_bw", "chunk_overhead_ms"}


# ---------------------------------------------------------------------------
# plan-key / cost-constant integration
# ---------------------------------------------------------------------------

def test_plan_key_chunk_overhead_backward_compatible():
    from repro.plan import plan_key
    kw = dict(n_seq=2, seq_len=64, d_model=128, capacity=32, top_k=2,
              num_experts=4, mode="vanilla", objective="traffic",
              exec_mode="sync", pipeline_chunks=4, comm_mode="flat",
              topo=None, M=4)
    legacy = plan_key(**kw)
    assert plan_key(**kw, chunk_overhead_ms=-1.0) == legacy   # default
    assert plan_key(**kw, chunk_overhead_ms=0.0) == legacy    # unset
    calibrated = plan_key(**kw, chunk_overhead_ms=0.07)
    assert calibrated != legacy and calibrated.endswith("_o0.07")


def test_resolve_chunk_overhead_ms():
    from repro.sched.cost import (DEFAULT_CHUNK_OVERHEAD_MS,
                                  resolve_chunk_overhead_ms)
    assert resolve_chunk_overhead_ms(None) == DEFAULT_CHUNK_OVERHEAD_MS
    assert resolve_chunk_overhead_ms(-1.0) == DEFAULT_CHUNK_OVERHEAD_MS
    assert resolve_chunk_overhead_ms(0.0) == DEFAULT_CHUNK_OVERHEAD_MS
    assert resolve_chunk_overhead_ms(0.2) == 0.2
    # the config default means "use the built-in constant"
    assert resolve_chunk_overhead_ms(LuffyConfig().chunk_overhead_ms) \
        == DEFAULT_CHUNK_OVERHEAD_MS


def test_finalize_metrics_masks_and_floats():
    import numpy as np
    from repro import train_lib
    m = train_lib.finalize_metrics(
        {"loss": np.float32(1.5), "inter_bytes_shipped": np.float32(0.0),
         "bucket": 1}, LuffyConfig(comm_mode="hier"))
    assert m["loss"] == 1.5 and isinstance(m["loss"], float)
    assert m["inter_bytes_shipped"] is None
    assert m["bucket"] == 1.0


# the step's 20 scalar metrics: tf.forward_train's 17, optim.update's two,
# and the total loss
STEP_METRIC_NAMES = {
    "loss", "aux_loss", "dispatch_drop", "combine_drop", "condense_rate",
    "local_frac", "traffic_before", "traffic_after", "inter_bytes_flat",
    "inter_bytes_dedup", "inter_bytes_shipped", "plans_built",
    "plans_reused", "plan_reuse_mismatch", "measured_pairs",
    "condense_built", "condense_reused", "grad_norm", "lr", "total_loss"}


@pytest.fixture(scope="module", params=[True, False],
                ids=["condense", "no_condense"])
def tiny_step(request):
    """One CPU train step of a tiny MoE model under a hier / dense-wire
    config: (jitted step, its inputs, its outputs, the config)."""
    import jax
    import jax.numpy as jnp
    from repro import optim, train_lib
    from repro.config import OptimConfig, ShapeConfig, reduced
    from repro.configs import get_config
    from repro.core.moe_layer import capacity_for
    from repro.data import SyntheticLM
    from repro.dist import single_device
    from repro.models.model import build_model
    cfg = reduced(get_config("moe-gpt2"), num_layers=2)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    luffy = LuffyConfig(enable_condensation=request.param,
                        condense_group=64, comm_mode="hier")
    ocfg = OptimConfig()
    cap = capacity_for(cfg.moe, 4 * 128, cfg.moe.num_experts)
    step = jax.jit(train_lib.make_train_step(cfg, luffy, ocfg,
                                             single_device(), cap))
    batch = {k: jnp.asarray(v) for k, v in
             SyntheticLM(cfg, ShapeConfig("t", 128, 4, "train"))
             .batch(0).items()}
    args = (params, optim.init_opt_state(params, ocfg),
            train_lib.init_luffy_state(), batch)
    return step, args, step(*args), luffy


def test_finalize_metrics_one_pull_equals_per_key_pull(tiny_step):
    import struct
    from repro import train_lib
    _, _, out, luffy = tiny_step
    m = out[3]
    base = train_lib.PULLS
    packed = train_lib.finalize_metrics(m, luffy)
    assert train_lib.PULLS - base == 1
    base = train_lib.PULLS
    per_key = train_lib.finalize_metrics(dict(m), luffy)
    assert train_lib.PULLS - base == len(STEP_METRIC_NAMES)

    def bits(d):
        return [(k, None if v is None else struct.pack("<d", v))
                for k, v in d.items()]

    assert bits(packed) == bits(per_key)
    assert all(isinstance(v, float) for k, v in packed.items()
               if k != "inter_bytes_shipped")
    # hier comm over the dense wire: nothing measured, not 0 bytes
    assert packed["inter_bytes_shipped"] is None
    assert (packed["condense_rate"] > 0.0) == luffy.enable_condensation


def test_step_metrics_reads_like_the_old_dict(tiny_step):
    import jax
    import jax.numpy as jnp
    from repro import train_lib
    step, args, out, _ = tiny_step
    m = out[3]
    assert isinstance(m, train_lib.StepMetrics)
    assert set(m.keys()) == STEP_METRIC_NAMES and len(m) == 20
    assert "_wire_ef" not in m
    loss = m["loss"]
    assert isinstance(loss, jax.Array) and loss.shape == ()
    assert float(loss) == float(m.values[list(m).index("loss")])
    assert set(dict(m)) == STEP_METRIC_NAMES
    # one f32 leaf, names in the static aux data
    leaves, treedef = jax.tree.flatten(m)
    assert len(leaves) == 1 and leaves[0].shape == (20,)
    assert leaves[0].dtype == jnp.float32
    again = jax.tree.unflatten(treedef, leaves)
    assert list(again) == list(m) and again.names == m.names
    assert jax.block_until_ready(m) is not None
    assert list(jax.jit(lambda x: x)(m)) == list(m)

    def passthrough(p, o, lstate, batch):   # as the harness's fault wrapper
        res = step(p, o, lstate, batch)
        return res[0], res[1], res[2], res[3]

    m2 = jax.block_until_ready(passthrough(*args))[3]
    assert isinstance(m2, train_lib.StepMetrics)
    assert float(m2["loss"]) == float(m["loss"])


def test_step_metrics_packs_only_f32_scalars():
    import jax.numpy as jnp
    from repro import train_lib
    # an int count above 2**24 would round through an f32 slot
    for bad in (jnp.int32(2 ** 24 + 1), jnp.zeros(3), jnp.bfloat16(1.0)):
        with pytest.raises(ValueError, match="not an f32 scalar"):
            train_lib.StepMetrics.pack({"loss": jnp.float32(1.0), "x": bad})


# ---------------------------------------------------------------------------
# 8-device: traced probe exchange (subprocess)
# ---------------------------------------------------------------------------

def test_traced_exchange_8dev():
    """--trace invariants on a real hier exchange: every instrumented
    phase fires, the inclusive 'exchange' span covers the sum of its
    children's EXCLUSIVE times, and a jitted step records no phase
    spans (scan bodies are structurally silent)."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import json
        import jax, jax.numpy as jnp
        from repro.config import LuffyConfig, reduced
        from repro.configs import get_config
        from repro.obs import trace as obs_trace
        from repro.obs.calibrate import probe_exchange

        cfg = reduced(get_config("moe-gpt2"), num_layers=2, d_model=64,
                      max_experts=4, seq_len_hint=32)
        luffy = LuffyConfig(enable_condensation=True,
                            enable_migration=True, condense_group=32)
        tr = obs_trace.activate(obs_trace.Tracer(fence=True))
        probe_exchange(cfg, luffy, seq_len=32)
        obs_trace.deactivate()
        names = {e["name"] for e in tr.spans()}
        required = {"plan_build", "condense", "dispatch", "expert_ffn",
                    "combine", "exchange"}
        assert required <= names, (required - names, names)
        (ex,) = tr.spans("exchange")
        t0, t1 = ex["ts"], ex["ts"] + ex["dur"]
        child_excl = sum(
            e["args"]["self_us"] for e in tr.spans()
            if e is not ex and e["ts"] >= t0 - 1e-6
            and e["ts"] + e["dur"] <= t1 + 1e-6)
        assert ex["dur"] >= child_excl - 1e-3, (ex["dur"], child_excl)

        tr2 = obs_trace.activate(obs_trace.Tracer(fence=True))
        def step(x):
            def body(c, _):
                with obs_trace.phase("scan_phase"):
                    c = c * 2.0
                return c, c
            out, _ = jax.lax.scan(body, x, None, length=3)
            return out
        jax.jit(step)(jnp.float32(1.0))
        obs_trace.deactivate()
        assert tr2.spans() == [], tr2.spans()
        print("OK8")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", script], cwd=ROOT,
                         capture_output=True, text=True, env=env,
                         timeout=1200)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK8" in out.stdout
