"""Golden schema of the dryrun comm-traffic ledger (DESIGN.md §11).

The ledger JSON is a consumed artifact (benchmarks, CI uploads, the
--metrics-json flattening), so its shape is versioned: this test pins
``schema_version`` and the exact key sets of every section. Renaming or
adding a key MUST bump ``repro.obs.metrics.COMM_LEDGER_SCHEMA_VERSION``
and update the goldens here."""
import types

import numpy as np
import pytest

from repro.config import SHAPES
from repro.configs import get_config

from repro.launch.dryrun import comm_traffic_ledger
from repro.obs.calibrate import Calibration, calibration_key
from repro.obs.metrics import COMM_LEDGER_SCHEMA_VERSION

TOP_KEYS = {"schema_version", "calibration", "topology", "dedup_factor",
            "buckets", "wire", "plan_reuse", "condensation", "decode",
            "autotune"}
TOPOLOGY_KEYS = {"nodes", "devices_per_node", "bw_ratio"}
BUCKET_KEYS = {"flat", "hier", "overlap"}
TIER_KEYS = {"intra_bytes", "inter_bytes", "time_s"}
OVERLAP_KEYS = {"ffn_ms", "sync_ms", "pipelined_ms", "chunks", "speedup"}
PLAN_REUSE_KEYS = {"mode", "moe_sublayers", "n_slots",
                   "plans_built_per_step", "plans_reused_per_step",
                   "revalidation_mismatches", "planning_ms_per_plan",
                   "revalidate_ms_per_check",
                   "planning_ms_saved_per_step"}
CONDENSATION_KEYS = {"backend", "group_size", "lsh_bits",
                     "measured_pairs_per_step",
                     "similarity_ms_per_build", "dedup_wire",
                     "condense_plan"}
DEDUP_WIRE_KEYS = {"enabled", "modeled_inter_bytes", "flat_inter_bytes",
                   "shipped_inter_bytes"}
CONDENSE_PLAN_KEYS = {"mode", "built_per_step", "reused_per_step",
                      "similarity_ms_saved_per_step"}
DECODE_KEYS = {"tokens", "combine_ms", "shared_ffn_ms", "sync_ms",
               "overlap_ms", "modeled_speedup"}
AUTOTUNE_KEYS = {"applied", "key", "knobs", "modeled_step_ms",
                 "default_step_ms", "modeled_savings_ms", "candidates"}
KNOB_KEYS = {"comm_mode", "hier_dedup", "exec_mode", "pipeline_chunks",
             "plan_objective", "similarity_backend", "lsh_bits",
             "wire_dtype"}
WIRE_KEYS = {"dtype", "precision", "row_bytes", "row_bytes_f32",
             "scale_block", "shipped_vanilla_bytes",
             "shipped_migrate_bytes", "shipped_pipelined_bytes"}


def _fake_mesh(shape_by_axis):
    return types.SimpleNamespace(
        axis_names=tuple(shape_by_axis),
        devices=np.zeros(tuple(shape_by_axis.values())))


def _ledger(**kw):
    cfg = get_config("moe-gpt2")
    return comm_traffic_ledger(cfg, SHAPES["train_4k"],
                               _fake_mesh({"data": 16, "model": 16}),
                               nodes=4, **kw)


def test_ledger_schema_version_and_key_sets():
    led = _ledger()
    assert led["schema_version"] == COMM_LEDGER_SCHEMA_VERSION == 6
    assert set(led) == TOP_KEYS
    assert set(led["topology"]) == TOPOLOGY_KEYS
    assert set(led["buckets"]) == {"0.0", "0.25", "0.5"}
    for b in led["buckets"].values():
        assert set(b) == BUCKET_KEYS
        assert set(b["flat"]) == set(b["hier"]) == TIER_KEYS
        assert set(b["overlap"]) == OVERLAP_KEYS
    assert set(led["wire"]) == WIRE_KEYS
    # default run: identity wire — precision exactly 1, bytes unscaled
    assert led["wire"]["dtype"] == "f32"
    assert led["wire"]["precision"] == 1.0
    assert led["wire"]["row_bytes"] == led["wire"]["row_bytes_f32"]
    # v6: per-execution-mode shipped bytes — equal by construction
    # (dispatch dedup is mode-independent; the keys exist to record
    # that the wire's mode scope is closed, DESIGN.md §15)
    w = led["wire"]
    assert (w["shipped_vanilla_bytes"] == w["shipped_migrate_bytes"]
            == w["shipped_pipelined_bytes"])
    assert set(led["plan_reuse"]) == PLAN_REUSE_KEYS
    assert set(led["condensation"]) == CONDENSATION_KEYS
    assert set(led["condensation"]["dedup_wire"]) == DEDUP_WIRE_KEYS
    assert set(led["condensation"]["condense_plan"]) == \
        CONDENSE_PLAN_KEYS
    assert set(led["decode"]) == DECODE_KEYS
    # decode step cost: overlap hides the shorter leg behind the longer
    dec = led["decode"]
    assert dec["overlap_ms"] <= dec["sync_ms"]
    assert dec["modeled_speedup"] >= 1.0
    assert set(led["autotune"]) == AUTOTUNE_KEYS
    assert set(led["autotune"]["knobs"]) == KNOB_KEYS
    assert led["autotune"]["applied"] is False   # modeled, not resolved
    # defaults are always in the grid: tuned can never model worse
    assert led["autotune"]["modeled_step_ms"] <= \
        led["autotune"]["default_step_ms"]
    assert led["autotune"]["modeled_savings_ms"] == pytest.approx(
        led["autotune"]["default_step_ms"]
        - led["autotune"]["modeled_step_ms"])
    assert led["calibration"] is None          # uncalibrated pricing


def test_ledger_wire_dtype_scales_bucket_bytes():
    """The compressed wire (DESIGN.md §14) shows up in the ledger as an
    exact 1/precision scaling of every modeled byte field."""
    base = _ledger()
    led = _ledger(wire_dtype="bf16")
    assert set(led) == TOP_KEYS
    prec = led["wire"]["precision"]
    assert prec > 1.0
    b, c = led["buckets"]["0.0"], base["buckets"]["0.0"]
    for tier in ("flat", "hier"):
        assert b[tier]["inter_bytes"] == pytest.approx(
            c[tier]["inter_bytes"] / prec)
        assert b[tier]["intra_bytes"] == pytest.approx(
            c[tier]["intra_bytes"] / prec)


def test_ledger_non_hier_and_non_moe_return_none():
    cfg = get_config("moe-gpt2")
    led = comm_traffic_ledger(cfg, SHAPES["train_4k"],
                              _fake_mesh({"data": 16, "model": 3}),
                              nodes=2)        # 3 % 2 != 0: no hier split
    assert led is None


def test_ledger_calibrated_pricing_same_schema():
    """Calibration swaps constants, never shape: same key sets, the
    artifact key recorded, and the measured numbers actually flow into
    the priced sections."""
    from repro.comm.topology import Topology
    base = _ledger()
    topo = Topology(4, 4)
    calib = Calibration(
        key=calibration_key(topo, 16, backend="cpu"),
        intra_bw=1e9, inter_bw=1e8, intra_lat=1e-5, inter_lat=1e-4,
        chunk_overhead_ms=0.5, plan_step_us=50.0, sim_speed=1e10,
        ffn_speed=1e12)
    led = _ledger(calibration=calib)
    assert set(led) == TOP_KEYS
    assert led["schema_version"] == COMM_LEDGER_SCHEMA_VERSION
    assert led["calibration"] == calib.key
    b0, c0 = led["buckets"]["0.0"], base["buckets"]["0.0"]
    # slower measured FFN roofline and slower links: times move
    assert b0["overlap"]["ffn_ms"] > c0["overlap"]["ffn_ms"]
    assert b0["hier"]["time_s"] > c0["hier"]["time_s"]
    assert led["plan_reuse"]["planning_ms_per_plan"] > \
        base["plan_reuse"]["planning_ms_per_plan"]
    sims = led["condensation"]["similarity_ms_per_build"]
    assert sims["exact"] > \
        base["condensation"]["similarity_ms_per_build"]["exact"]


def test_ledger_flattens_into_metrics_record():
    from repro.obs.metrics import flatten
    led = _ledger()
    flat = flatten("comm_ledger", led)
    assert flat["comm_ledger/schema_version"] == 6
    assert "comm_ledger/decode/modeled_speedup" in flat
    assert "comm_ledger/buckets/0.0/hier/inter_bytes" in flat
    assert "comm_ledger/plan_reuse/planning_ms_per_plan" in flat
    assert all(not isinstance(v, dict) for v in flat.values())
