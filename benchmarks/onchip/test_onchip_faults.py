"""A whole run of the harness on the CPU at a small size, past the
look for a chip: sound, ``correct`` is true; with the timed path broken
underneath (a step that returns its state unchanged, half of the batch
left out, a token altered as it is fed), in every rate bucket's
executable or in one alone, ``correct`` is false. The
control, the reference computed with int8 matrix products, reads
higher than the program on every number that separates them.

The limits here are for this small size, set from CPU readings of it
(seed 1: program at most 3e-4 on the losses, 0.007 on the first
gradient, 0.003 on the change; the faults at least 0.004, 0.2 and 1).
"""
import copy
import json
import time
from pathlib import Path

import jax
import pytest

import harness
import traffic

HERE = Path(__file__).resolve().parent
SEED = 1
LIMITS = {"loss_step1": 2e-3, "loss_step2": 2e-3, "loss_step3": 2e-3,
          "grad1": 0.05, "change": 0.03}


def tiny_cell():
    conf = json.loads((HERE / "configs" / "moe-gpt2-l4.json").read_text())
    conf.update(num_layers=2, d_model=64, num_heads=2, head_dim=32,
                vocab_size=512, num_experts=4, expert_d_ff=128)
    conf["reduced"] = ["num_layers", "d_model", "num_heads", "head_dim",
                       "vocab_size", "num_experts", "expert_d_ff"]
    t = {"generator": "zipf_repeat", "zipf_a": 1.2, "repeat_p": 0.3,
         "min_len_frac": 0.5, "seq_len": 128, "global_batch": 4, "pool": 6}
    e2e = [{"name": n, "unit": "u"} for n in
           ("tokens_per_s", "step_ms_p90", "setup_s")]
    return harness.Cell("tiny.1chip", 1, conf, t, e2e, [], LIMITS)


@pytest.fixture(scope="module")
def built():
    prog = harness.Program(tiny_cell(), jax.devices())
    prog.init_state(SEED)
    pool = traffic.make_pool(prog.cell.traffic, prog.cfg.vocab_size, SEED)
    prog.compile(pool[0])
    prog.free_state()
    return prog, harness.CompileCounter()


def run(built, fault=None, buckets=None):
    prog, counter = built
    p = copy.copy(prog)
    p.exes = dict(prog.exes)
    harness.plant(p, fault, buckets)
    return harness.run_cell(p, SEED, 0.3, False, time.perf_counter(),
                            counter)


def test_sound_run_is_correct(built):
    out = run(built)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["_info"]["compiles_in_window"] == 0
    assert set(out["metrics"]) == {"tokens_per_s", "step_ms_p90",
                                   "setup_s"}
    assert list(out)[-2:] == ["checks", "_info"]
    # condensation acts at this size: every rate bucket is checked
    assert {c.split(".")[0] for c in out["checks"]} == {"b0", "b1", "b2"}


@pytest.mark.parametrize("fault", ["stale_state", "half_batch", "token"])
def test_broken_timed_path_is_not_correct(built, fault):
    out = run(built, fault)
    assert not out["correct"], (fault, out["checks"])


@pytest.mark.parametrize("fault,bucket", [("stale_state", 1),
                                          ("half_batch", 2)])
def test_a_fault_in_one_rate_bucket_is_not_correct(built, fault, bucket):
    out = run(built, fault, {bucket})
    assert not out["correct"], (fault, bucket, out["checks"])
    failing = {k for k, c in out["checks"].items() if c["value"] > c["limit"]}
    assert {k.split(".")[0] for k in failing} == {f"b{bucket}"}


def test_control_reads_above_the_program():
    cell = tiny_cell()
    pool = traffic.make_pool(cell.traffic, cell.conf["vocab_size"], SEED)
    ref = harness.reference_side(cell, SEED, pool)
    ctl = harness.reference_side(cell, SEED, pool, lower=True)
    again = harness.reference_side(cell, SEED, pool)
    nums = harness.numbers(ctl, ref)
    assert harness.numbers(again, ref) == {k: 0.0 for k in nums}
    assert nums["grad1"] > LIMITS["grad1"]
    assert not harness.passed(harness.judge({0: nums}, LIMITS))
