"""BENCHMARK.json and the files it names: every cell resolves its
configuration, traffic, metric readers and limits by name; each
configuration file is the program's configuration as it is run; the
FLOP count per token matches a hand count; the traffic is the same work
for every seed; and the command refuses to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import flops
import harness
import traffic
import weights

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]
SPEC = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_resolves_every_file_by_name(workload):
    cell = harness.resolve(SPEC, workload)
    assert cell.chips in (1, 4)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(harness.load_module(
            HERE / "metrics" / f"{m['name']}.py").read)
    assert cell.limits and set(cell.limits) <= set(harness.NUMBERS)
    assert harness.reference_module(cell.conf).train3
    assert cell.traffic["global_batch"] % cell.chips == 0


@pytest.mark.parametrize("name", [c["name"] for c in SPEC["configs"]])
def test_config_file_is_the_program_config_it_runs(name):
    entry = next(c for c in SPEC["configs"] if c["name"] == name)
    conf = harness.load_config(SPEC, name)
    assert sorted(conf["reduced"]) == sorted(entry["reduced"])
    cfg = harness.program_config(conf)
    assert cfg.num_layers == conf["num_layers"]
    assert cfg.moe.d_ff == conf["expert_d_ff"]
    from repro.models.model import build_model
    weights.check_layout(harness.layout_module(conf), conf,
                         build_model(cfg).init_struct())


def test_a_size_changed_without_listing_it_is_refused():
    conf = harness.load_config(SPEC, "moe-gpt2-l4")
    with pytest.raises(ValueError, match="d_model"):
        harness.program_config(dict(conf, d_model=512))


def test_spec_follows_the_contract_shapes():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.25
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
        layers.setdefault(m["layer"], set()).add(m["name"])
    four = [w for w in SPEC["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(SPEC["workloads"]) // 2)
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


def hand_forward(L, d, E, k, f, V, lens):
    lens = np.asarray(lens, np.float64)
    ctx = np.sum(lens * (lens + 1) / 2) / np.sum(lens)
    attn = 2 * 4 * d * d
    experts = 2 * 3 * d * f * k          # gated: up, gate and down
    router = 2 * d * E
    scores = 4 * ctx * d
    return L * (attn + experts + router + scores) + 2 * V * d


@pytest.mark.parametrize("name,dims", [
    ("moe-gpt2-l4", (4, 768, 16, 2, 3072, 50257)),
    ("moe-transformerxl-l3", (3, 1024, 16, 2, 4096, 32000)),
])
def test_flops_per_token_match_a_hand_count(name, dims):
    conf = harness.load_config(SPEC, name)
    from repro.models.model import build_model
    struct = build_model(harness.program_config(conf)).init_struct()
    lens = [544, 608, 672, 736, 800, 864, 928, 992]
    got = flops.train_flops_per_token(harness.layout_module(conf), struct,
                                      conf, lens)
    assert got == pytest.approx(3 * hand_forward(*dims, lens), rel=1e-12)
    if name == "moe-gpt2-l4":
        assert 6.3e8 < got < 6.6e8


def test_traffic_is_the_same_work_for_every_seed():
    t = traffic.load(HERE / "traffic" / "zipf-s1024-b8.json")
    assert traffic.lengths(t).tolist() == [544, 608, 672, 736, 800, 864,
                                           928, 992]
    a = traffic.make_pool(dict(t, pool=3), 1000, 2 ** 40 + 3)
    b = traffic.make_pool(dict(t, pool=3), 1000, 2 ** 40 + 3)
    c = traffic.make_pool(dict(t, pool=3), 1000, 5)
    for x, y, z in zip(a, b, c):
        assert all(np.array_equal(x[k], y[k]) for k in x)
        assert (x["labels"] >= 0).sum() == (z["labels"] >= 0).sum() == 6144
        assert not np.array_equal(x["tokens"], z["tokens"])
    assert not np.array_equal(a[0]["tokens"], a[1]["tokens"])


def test_weights_from_large_seeds_differ_and_repeat():
    big = 2 ** 31 + 7
    k1, k2 = weights.seed_key(big), weights.seed_key(2 ** 32 + big)
    assert not np.array_equal(np.asarray(k1), np.asarray(k2))
    assert np.array_equal(np.asarray(k1), np.asarray(weights.seed_key(big)))


def run_cmd(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "benchmarks/onchip/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_command_without_a_tpu_fails_and_prints_no_result():
    r = run_cmd(CHECKOUT, {"PYTHONPATH": str(CHECKOUT / "src")})
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "TPU" in r.stderr


def test_command_without_the_program_fails_and_prints_no_result(tmp_path):
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(HERE, tmp_path / "benchmarks" / "onchip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = run_cmd(tmp_path, {"PYTHONPATH": ""})
    assert r.returncode != 0
    assert r.stdout.strip() == ""
