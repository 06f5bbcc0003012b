"""Device time per named scope: the class rules, the ``op_name`` map of
a compiled module, the choice of each step's map by the host ``step``
span an operation falls in, the division per step and per chip, and the
attribution of a recorded chip trace."""
import gzip
import json
import time
from pathlib import Path

import pytest

import scopes
import tracereduce as tr

HERE = Path(__file__).resolve().parent


@pytest.mark.parametrize("op_name,cls", [
    ("jit(step)/optimizer/sub", "optimizer"),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "exchange/expert_ffn/erd,edf->erf/dot_general", "expert_ffn"),
    ("jit(step)/jvp()/while/body/closed_call/exchange/dispatch/all_to_all",
     "dispatch_combine"),
    ("jvp())/while/body/closed_call/exchange/dispatch_pack/gather",
     "dispatch_combine"),
    ("jit(step)/jvp()/while/body/closed_call/exchange/combine_unpack/gather",
     "dispatch_combine"),
    ("jit(step)/jvp()/while/body/closed_call/router/top_k", "moe_plan"),
    ("plan_build/condense/cond/branch_0_fun/reduce_sum", "moe_plan"),
    ("checkpoint/rematted_computation/plan_build/jit(take_along_axis)/gather",
     "moe_plan"),
    ("jit(step)/jvp()/while/body/closed_call/checkpoint/rematted_computation"
     "/attention/bqhd,bkhd->bhqk/dot_general", "attention"),
    ("jit(step)/transpose(jvp(embed))/mul", "lm_head"),
    ("jit(step)/transpose(jvp(lm_head))/while/body/closed_call/dot_general",
     "lm_head"),
    # first match wins: an optimizer op on an expert weight is optimizer
    ("jit(step)/optimizer/expert_ffn/mul", "optimizer"),
    ("jit(step)/transpose(jvp())/while/body/squeeze", "other"),
    ("params['embed']['table']", "other"),          # an argument's path
    ("jit(step)/jvp()/while/body/closed_call/exchange/dispatch_positions",
     "other"),                                       # a word, not a scope
    ("", "other"),
])
def test_the_first_class_whose_scope_is_on_the_path(op_name, cls):
    assert scopes.classify(op_name) == cls


HLO = """\
HloModule jit_step, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%fused_computation.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %tanh.3 = f32[4]{0} tanh(%p), metadata={op_name="jit(step)/attention/tanh" source_file="m.py" source_line=3}
}

ENTRY %main.9 (x.1: f32[4]) -> f32[4] {
  %x.1 = f32[4]{0} parameter(0), metadata={op_name="params[\\'embed\\']"}
  %copy.2 = f32[4]{0} copy(%x.1)
  ROOT %fusion.1 = f32[4]{0} fusion(%copy.2), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/attention/tanh" source_file="m.py" source_line=3}
}
"""


def test_op_names_of_a_module_text():
    m = scopes.op_names(HLO)
    assert m == {"p": "", "tanh.3": "jit(step)/attention/tanh",
                 "x.1": "params[\\'embed\\']", "copy.2": "",
                 "fusion.1": "jit(step)/attention/tanh"}
    assert scopes.scopes_seen({0: m}) == ["attention"]
    assert scopes.scopes_seen({0: {"copy.2": "", "x.1": "params['embed']"}}
                              ) == []


def hand_trace():
    # window [0, 100) us, two chips, two steps: step 0 (bucket 0) 0-40,
    # step 1 (bucket 1) 50-90. "fusion.1" is the FFN in bucket 0's
    # module and the optimizer in bucket 1's; "copy.9" is in no map;
    # the op at 42-47 runs between the steps; "while.4" is no leaf.
    us = 1000
    dev0 = tr.mark_leaves([
        [0, 10 * us, "fusion.1 bf16[16,8]"],
        [10 * us, 30 * us, "fusion.2 f32[8]"],
        [42 * us, 47 * us, "fusion.2 f32[8]"],
        [50 * us, 90 * us, "while.4 f32[8]"],
        [50 * us, 60 * us, "fusion.1 bf16[16,8]"],
        [60 * us, 70 * us, "copy.9 f32[8]"]])
    dev1 = tr.mark_leaves([[0, 20 * us, "fusion.2 f32[8]"]])
    host = [[0, 40 * us, "step"], [40 * us, 50 * us, "metrics"],
            [50 * us, 90 * us, "step"], [90 * us, 100 * us, "metrics"]]
    maps = {0: {"fusion.1": "jit(step)/jvp()/exchange/expert_ffn/dot_general",
                "fusion.2": "jit(step)/transpose(jvp(lm_head))/dot_general"},
            1: {"fusion.1": "jit(step)/optimizer/mul",
                "fusion.2": "jit(step)/transpose(jvp(lm_head))/dot_general"}}
    return {"devices": {0: dev0, 1: dev1}, "host": host}, maps


def test_each_op_is_read_in_the_map_of_its_steps_bucket():
    rec, maps = hand_trace()
    r = scopes.attribute(rec, (0, 100_000), maps, [0, 1])
    assert r["steps"] == 2
    # per step (2) and mean over chips (2): ms = us / 4 / 1000
    ms = r["ms"]
    assert ms["expert_ffn"] == pytest.approx(10e-3 / 4)
    assert ms["optimizer"] == pytest.approx(10e-3 / 4)
    assert ms["lm_head"] == pytest.approx((20 + 20) * 1e-3 / 4)
    # between the steps and not in a map: other, and not found
    assert ms["other"] == pytest.approx((5 + 10) * 1e-3 / 4)
    assert sum(ms.values()) == pytest.approx(r["leaf_ms"])
    assert r["leaf_ms"] == pytest.approx(75e-3 / 4)
    assert r["found_share"] == pytest.approx(100 * 60 / 75)
    assert set(ms) == set(scopes.NAMES)
    assert r["top"]["optimizer"][0][:2] == ["fusion.1 bf16[16,8]",
                                            "jit(step)/optimizer/mul"]
    # the same ops read through the other bucket swap FFN and optimizer
    swapped = scopes.attribute(rec, (0, 100_000), maps, [1, 0])
    assert swapped["ms"]["expert_ffn"] == pytest.approx(ms["optimizer"])


def test_steps_that_cannot_be_paired_with_buckets_find_nothing():
    rec, maps = hand_trace()
    r = scopes.attribute(rec, (0, 100_000), maps, [0])
    assert r["found_share"] == 0.0
    assert r["ms"]["other"] == pytest.approx(r["leaf_ms"])


def test_a_program_without_scopes_sees_none():
    rec, maps = hand_trace()
    r = scopes.attribute(rec, (0, 100_000), maps, [0, 1])
    assert r["scopes_seen"] == ["expert_ffn", "lm_head", "optimizer"]
    plain = {b: {n: "jit(step)/dot_general" for n in m}
             for b, m in maps.items()}
    r0 = scopes.attribute(rec, (0, 100_000), plain, [0, 1])
    assert r0["found_share"] == r["found_share"]
    assert r0["scopes_seen"] == []
    assert r0["ms"]["other"] == pytest.approx(r0["leaf_ms"])


class Prog:
    """Takes steps through rate buckets 0, 0, 1, 0, ..., as given."""
    def __init__(self, buckets):
        self.buckets = list(buckets)
        self.exes = {}

    def step(self, batch, bucket=None):
        return {"bucket": self.buckets.pop(0), "batch": batch}


def test_recording_keeps_every_step_in_order():
    prog = Prog([0, 2, 1])
    taken = scopes.recording(prog)
    assert prog.step("a")["bucket"] == 0
    prog.step("b", bucket=2)
    prog.step("c")
    assert [(s["batch"], s["bucket"]) for s in taken] == [
        ("a", 0), ("b", 2), ("c", 1)]


def test_run_traced_reads_the_window_in_the_buckets_of_its_last_steps(
        monkeypatch):
    """The check steps come before the window: the window's steps are
    the last ``attempted`` the program took."""
    import harness
    rec, maps = hand_trace()
    prog = Prog([1, 1, 1, 0, 1])

    def run_cell(prog, seed, seconds, trace, t_start, counter, trace_dir):
        assert trace and trace_dir == "dir"
        for k in range(5):
            prog.step(k)
        return {"attempted": 2}

    monkeypatch.setattr(harness, "run_cell", run_cell)
    monkeypatch.setattr(tr, "load", lambda d: rec)
    monkeypatch.setattr(tr, "window_of", lambda r: (0, 100_000))
    monkeypatch.setattr(scopes, "op_names", lambda text: maps[int(text)])
    prog.exes = {b: type("Exe", (), {"as_text": lambda self, b=b: str(b)})()
                 for b in maps}
    out, r = scopes.run_traced(prog, 1, 8.0, 0.0, None, "dir")
    assert out == {"attempted": 2}
    assert r == {**scopes.attribute(rec, (0, 100_000), maps, [0, 1]),
                 "seconds": r["seconds"]}


def recorded():
    rec = json.loads(gzip.decompress(
        (HERE / "testdata" / "trace_gpt2_1chip.json.gz").read_bytes()))
    names = json.loads(gzip.decompress(
        (HERE / "testdata" / "opnames_gpt2_1chip.json.gz").read_bytes()))
    lo, hi = rec.pop("window")
    return rec, (lo, hi), {names["bucket"]: names["op_names"]}


def test_recorded_chip_trace_by_scope():
    """Two steps of the one-chip moe-gpt2 cell in rate bucket 2 (TPU v5e),
    with the op_name of each of its instructions: the program's bucket-2
    step compiled for a v5e, whose module is the traced one's but for
    instruction names and metadata, matched to the traced one's names
    instruction by instruction."""
    rec = json.loads(gzip.decompress(
        (HERE / "testdata" / "trace_gpt2_1chip.json.gz").read_bytes()))
    names = json.loads(gzip.decompress(
        (HERE / "testdata" / "opnames_gpt2_1chip.json.gz").read_bytes()))
    lo, hi = rec.pop("window")
    b = names["bucket"]
    r = scopes.attribute(rec, (lo, hi), {b: names["op_names"]}, [b, b])
    assert r["steps"] == 2
    assert r["found_share"] == pytest.approx(100.0)
    assert sum(r["ms"].values()) == pytest.approx(r["leaf_ms"], rel=1e-9)
    busy_ms = tr.reduce(rec, (lo, hi))["busy_s"][0] * 1e3 / 2
    assert r["leaf_ms"] == pytest.approx(busy_ms, rel=0.03)
    assert r["scopes_seen"] == sorted(
        {s for _, ss in scopes.CLASSES for s in ss} - {"combine"})
    # AdamW on the three f32 expert weights, the attention softmax
    top = {c: [op.split(" ")[0] for op, _, _ in t]
           for c, t in r["top"].items()}
    assert top["optimizer"] == ["fusion.562", "fusion.564", "fusion.566"]
    assert top["attention"][0] == "fusion.1028"
    for cls in ("optimizer", "expert_ffn", "attention", "lm_head"):
        assert r["ms"][cls] > 10.0, (cls, r["ms"])


def test_of_run_reads_the_maps_of_the_compiled_steps():
    """The executables' own text gives the maps: a CPU-compiled step of
    a tiny moe-gpt2 names its expert FFN, and the seconds are counted."""
    import jax
    import harness
    import test_onchip_faults as faults
    import traffic
    prog = harness.Program(faults.tiny_cell(), jax.devices())
    prog.init_state(1)
    pool = traffic.make_pool(prog.cell.traffic, prog.cfg.vocab_size, 1)
    prog.compile(pool[0], [0])
    ffn = [n for n, op in scopes.op_names(prog.exes[0].as_text()).items()
           if scopes.classify(op) == "expert_ffn"]
    assert ffn
    rec = {"devices": {0: tr.mark_leaves([[10, 20, ffn[0] + " f32[8]"]])},
           "host": [[0, 30, "step"]]}
    t0 = time.perf_counter()
    r = scopes.of_run(rec, (0, 30), prog.exes, [{"bucket": 0}])
    assert r["ms"]["expert_ffn"] == pytest.approx(10e-6)
    assert r["found_share"] == 100.0
    assert 0 < r["seconds"] <= time.perf_counter() - t0


def traced_record(scopes_result):
    import harness
    return harness.RunRecord(steps=[], window_s=1.0, chips=1,
                             device_kind="TPU v5 lite", flops_per_token=1.0,
                             trace={"scopes": scopes_result})


@pytest.mark.parametrize("cls", [c for c, _ in scopes.CLASSES])
def test_each_scope_reader_reads_its_class_of_the_recorded_trace(cls):
    """``metrics/<class>_ms.py`` gives the class that ``attribute`` gives
    on the recorded chip trace; nothing where the program names no scope
    of the class, or where the run is not traced."""
    import harness
    rec, window, maps = recorded()
    b = next(iter(maps))
    r = scopes.attribute(rec, window, maps, [b, b])
    reader = harness.load_module(HERE / "metrics" / f"{cls}_ms.py")
    assert reader.read(traced_record(r)) == r["ms"][cls] > 0
    plain = {b: {n: "jit(step)/dot_general" for n in maps[b]}}
    r0 = scopes.attribute(rec, window, plain, [b, b])
    assert reader.read(traced_record(r0)) is None
    untraced = traced_record(r)
    untraced.trace = None
    assert reader.read(untraced) is None


def test_the_six_readers_and_other_add_up_to_the_leaf_time():
    import harness
    rec, window, maps = recorded()
    b = next(iter(maps))
    r = scopes.attribute(rec, window, maps, [b, b])
    got = [harness.load_module(HERE / "metrics" / f"{c}_ms.py").read(
        traced_record(r)) for c, _ in scopes.CLASSES]
    assert len(got) == 6
    assert sum(got) + r["ms"]["other"] == pytest.approx(r["leaf_ms"],
                                                        rel=1e-9)
    spec = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
    per_layer = {m["name"]: m for m in spec["per_layer"]}
    for c, _ in scopes.CLASSES:
        m = per_layer[f"{c}_ms"]
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            "ms", "lower", "device_trace", "tokens_per_s")
        assert "workloads" not in m


def test_a_layouts_classes_are_matched_first():
    """An architecture's ``SCOPE_CLASSES`` come before ``CLASSES``: a
    scope they name takes their class, the rest keep theirs."""
    rec, maps = hand_trace()
    base = scopes.attribute(rec, (0, 100_000), maps, [0, 1])
    extra = (("head_out", ("lm_head",)),)
    r = scopes.attribute(rec, (0, 100_000), maps, [0, 1],
                         extra + scopes.CLASSES)
    assert set(r["ms"]) == set(scopes.NAMES) | {"head_out"}
    assert r["ms"]["head_out"] == pytest.approx(base["ms"]["lm_head"])
    assert r["ms"]["lm_head"] == 0.0
    for c in ("expert_ffn", "optimizer", "other"):
        assert r["ms"][c] == pytest.approx(base["ms"][c])
    assert r["scopes_seen"] == base["scopes_seen"]
    assert scopes.class_ms(traced_record(r), "head_out", extra) == \
        pytest.approx(base["ms"]["lm_head"])


def test_a_traced_run_prints_its_scopes_before_the_result(capsys):
    import run
    out = {"correct": True, "attempted": 1, "failed": 0,
           "metrics": {}, "device": {}, "checks": {},
           "_info": {"setup_s": 1.0, "compiles_in_window": 0, "steps": 1,
                     "window_s": 1.0, "buckets": [0], "max_drop": 0.0,
                     "flops_per_token": 2.0, "program_losses": {},
                     "reference_losses": {}, "timeline": [], "memory": {},
                     "scopes": {"ms": {"attention": 3.0}}}}
    run.report(out)
    lines = capsys.readouterr().out.strip().splitlines()
    assert "flops_per_token=2.0" in lines[0]
    assert lines[-2] == 'scopes {"ms": {"attention": 3.0}}'
    assert json.loads(lines[-1])["correct"] is True
