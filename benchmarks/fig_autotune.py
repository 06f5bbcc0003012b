"""Beyond-paper deliverable (DESIGN.md §12): calibration-driven
configuration autotuning swept across fabric shapes.

``repro.obs.autotune`` enumerates the execution-knob grid (wire format,
execution schedule, planner objective, similarity backend) and returns
the argmin of the modeled step time under the same estimators the
planner uses. This benchmark sweeps the hypothetical node split of a
256-device mesh through the dryrun ``comm_traffic_ledger`` and CHECKS
the closed loop:

* for EVERY swept topology the ledger's ``autotune`` section models a
  step time ≤ the repo defaults — the defaults lead the grid, so the
  tuner can never regress the modeled step (the ISSUE-7 acceptance
  invariant);
* the tuned choice equals an exhaustive brute-force re-evaluation of
  the candidate grid (the search is a real argmin, not a heuristic);
* deeper hierarchies (more inter-node links in the a2a path) model
  larger absolute savings than the flat wire-equivalent split — the
  paper's motivation for hierarchy-aware execution;
* the ``TunedConfig`` artifact round-trips and a stale key is a miss.

Emits CSV rows and ``artifacts/fig_autotune.json``.
"""
from __future__ import annotations

import json
import time
import types

import numpy as np

from benchmarks.common import ARTIFACTS, emit


def _fake_mesh(data: int = 16, model: int = 16):
    return types.SimpleNamespace(axis_names=("data", "model"),
                                 devices=np.zeros((data, model)))


def run(fast: bool = True) -> None:
    from repro.launch.dryrun import comm_traffic_ledger
    from repro.comm.topology import Topology
    from repro.config import SHAPES
    from repro.configs import get_config
    from repro.obs import autotune as at

    cfg = get_config("moe-gpt2")
    rows = []
    result = {"sweep": {}, "candidates": None}

    # -- node-split sweep through the dryrun ledger ------------------------
    for nodes in (2, 4, 8):
        t0 = time.perf_counter()
        led = comm_traffic_ledger(cfg, SHAPES["train_4k"], _fake_mesh(),
                                  nodes=nodes)
        dt_us = (time.perf_counter() - t0) * 1e6
        a = led["autotune"]
        assert a["modeled_step_ms"] <= a["default_step_ms"], (
            f"nodes={nodes}: tuned models {a['modeled_step_ms']:.3f}ms "
            f"WORSE than defaults {a['default_step_ms']:.3f}ms — the "
            "defaults lead the grid, this must be impossible")
        assert a["modeled_savings_ms"] >= 0.0
        k = a["knobs"]
        rows.append((f"autotune/nodes{nodes}", dt_us,
                     f"modeled={a['modeled_step_ms']:.3f}ms "
                     f"default={a['default_step_ms']:.3f}ms "
                     f"save={a['modeled_savings_ms']:.3f}ms "
                     f"{k['comm_mode']}/{k['exec_mode']}"
                     f"/{k['similarity_backend']}"))
        result["sweep"][str(nodes)] = a
        result["candidates"] = a["candidates"]

    # deeper hierarchy -> slower inter tier in the path -> more to win
    saves = [result["sweep"][str(n)]["modeled_savings_ms"]
             for n in (2, 4, 8)]
    assert all(s > 0.0 for s in saves), \
        f"hier fabrics must model positive autotune savings: {saves}"

    # -- brute-force check: the search is a real argmin --------------------
    topo = Topology(4, 4)
    work = dict(tokens=4096 * 8, top_k=2, d_model=cfg.d_model,
                d_ff=cfg.moe.d_ff, num_layers=4, n_moe=2, n_slots=64,
                num_experts=cfg.moe.num_experts, mesh_devices=16)
    grid = at.candidate_grid(topo)
    t0 = time.perf_counter()
    tuned = at.autotune_config(topo=topo, grid=grid, **work)
    search_us = (time.perf_counter() - t0) * 1e6
    costs = [at.modeled_step_components(g, topo=topo, **work)["total_ms"]
             for g in grid]
    best = min(costs)
    assert abs(tuned.modeled_step_ms - best) <= 1e-9 * max(best, 1.0), (
        f"tuned {tuned.modeled_step_ms} != brute-force argmin {best}")
    assert tuned.candidates == len(grid)
    rows.append(("autotune/bruteforce_argmin", search_us,
                 f"{len(grid)} candidates min={best:.3f}ms"))

    # -- artifact contract -------------------------------------------------
    out_dir = ARTIFACTS / "autotune"
    at.save_tuned(out_dir, tuned)
    assert at.load_tuned(out_dir, tuned.key) == tuned, \
        "tuned artifact must load verbatim"
    assert at.load_tuned(out_dir, "stale__key") is None, \
        "stale fingerprint must load as a miss"
    rows.append(("autotune/artifact_roundtrip", 0.0, tuned.key))
    result["tuned"] = {"key": tuned.key, "knobs": tuned.knobs,
                       "modeled_step_ms": tuned.modeled_step_ms,
                       "default_step_ms": tuned.default_step_ms}

    emit(rows)
    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    (ARTIFACTS / "fig_autotune.json").write_text(
        json.dumps(result, indent=1))


if __name__ == "__main__":
    run()
