"""Beyond-paper deliverable (DESIGN.md §15): the universal dedup wire
swept across execution mode × expert skew.

Two contracts, both pinned at modeled-pricing level (the executed twins
live in ``tests/test_wire_dtype.py`` / ``tests/test_condense.py``):

* **mode sweep** — with ``hier_dedup="on"`` the shipped inter-node
  bytes drop STRICTLY below the dense (flat) wire in every execution
  mode — vanilla, migrate, pipelined — and the three per-mode ledger
  numbers coincide (dispatch dedup is mode-independent: experts never
  move, so the (token, node) unique packing is the same). With the
  wire off, shipped == flat in all three.
* **skew sweep** — the "replicate" planner objective (HierMoE-style
  intra-node hot-expert replication) is NEVER worse than the
  migration-only "traffic" objective under the modeled exposed time,
  and STRICTLY better once the hottest expert's demand reaches
  ``REPLICATE_SKEW_MIN`` (2×) the mean — the regime where re-homing
  whole sequences cannot split one expert's serialized demand. The
  model is exactly the planner's own arithmetic: relief
  ``ffn_ms · hot_share / 2`` against
  ``repro.plan.estimate.replica_consistency_ms``.

Emits CSV rows and ``artifacts/fig_dedup_universal.json``.
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
    import jax.numpy as jnp

    from repro.comm.topology import Topology
    from repro.config import SHAPES
    from repro.configs import get_config
    from repro.plan.estimate import replica_consistency_ms
    from repro.plan.objectives import (REPLICATE_SKEW_MIN,
                                       plan_expert_replicas)

    cfg = get_config("moe-gpt2")
    rows = []
    result = {"modes": {}, "skew": {}}

    # ---- mode sweep: the dedup wire is universal -------------------------
    MODE_KEYS = ("shipped_vanilla_bytes", "shipped_migrate_bytes",
                 "shipped_pipelined_bytes")
    for nodes in (2, 4, 8):
        t0 = time.perf_counter()
        on = comm_traffic_ledger(cfg, SHAPES["train_4k"], _fake_mesh(),
                                 nodes=nodes, hier_dedup="on")
        off = comm_traffic_ledger(cfg, SHAPES["train_4k"], _fake_mesh(),
                                  nodes=nodes)
        dt_us = (time.perf_counter() - t0) * 1e6
        dense = off["buckets"]["0.0"]["flat"]["inter_bytes"]
        shipped = [on["wire"][k] for k in MODE_KEYS]
        # one number covers vanilla + migrate + pipelined …
        assert len(set(shipped)) == 1, (nodes, shipped)
        # … and it drops STRICTLY below the dense wire in every mode
        for k, s in zip(MODE_KEYS, shipped):
            assert s < dense, (nodes, k, s, dense)
        # wire off: every mode ships the dense bytes
        assert all(off["wire"][k] == dense for k in MODE_KEYS), nodes
        factor = dense / max(shipped[0], 1.0)
        rows.append((f"dedup_universal/nodes{nodes}", dt_us,
                     f"dense={dense:.3g}B shipped={shipped[0]:.3g}B "
                     f"x{factor:.2f}"))
        result["modes"][str(nodes)] = {
            "dense_inter_bytes": dense,
            "shipped_inter_bytes": shipped[0],
            "dedup_factor": factor,
        }

    # ---- skew sweep: replication vs migration-only -----------------------
    # The planner's own exposed-time arithmetic: the hottest expert
    # serializes ffn_ms·(load/total) of the FFN stage; a replica halves
    # that at replica_consistency_ms per step. "traffic" (migration
    # only) cannot split one expert's demand, so its exposed time IS the
    # unrelieved hot share.
    topo = Topology(2, 4)
    e_local = 2
    E = e_local * topo.num_devices
    d, dff = cfg.d_model, cfg.moe.d_ff
    cost_ms = replica_consistency_ms(1, d, dff, topo=topo)
    ffn_ms = 3.0 * E * cost_ms     # relief at 2x skew = 3·cost > cost
    base = 100.0
    for skew in (1.0, 1.5, 2.0, 4.0, 8.0):
        # hot/mean == skew exactly: hot = skew·b·(E-1)/(E-skew)
        hot = skew * base * (E - 1) / (E - skew)
        load = np.full((E,), base, np.float32)
        load[0] = hot
        t0 = time.perf_counter()
        rep = np.asarray(plan_expert_replicas(
            jnp.asarray(load), e_local=e_local, topo=topo, ffn_ms=ffn_ms,
            d_model=d, d_ff=dff))
        dt_us = (time.perf_counter() - t0) * 1e6
        n_rep = int((rep >= 0).sum())
        hot_share = float(load.max() / load.sum())
        t_traffic = ffn_ms * hot_share
        relief = ffn_ms * hot_share / 2.0
        t_rep = t_traffic - (relief - cost_ms * n_rep if n_rep else 0.0)
        # never worse than migration-only …
        assert t_rep <= t_traffic + 1e-9, (skew, t_rep, t_traffic)
        if skew >= REPLICATE_SKEW_MIN:
            # … and strictly better at >= 2x skew
            assert n_rep >= 1 and t_rep < t_traffic, (skew, n_rep)
        else:
            # below the gate nothing replicates (consistency not paid)
            assert n_rep == 0 and t_rep == t_traffic, (skew, n_rep)
        rows.append((f"dedup_universal/skew{skew:g}", dt_us,
                     f"replicas={n_rep} traffic={t_traffic:.3f}ms "
                     f"replicate={t_rep:.3f}ms"))
        result["skew"][f"{skew:g}"] = {
            "replicas": n_rep, "exposed_traffic_ms": t_traffic,
            "exposed_replicate_ms": t_rep,
            "consistency_ms": cost_ms * n_rep,
        }

    emit(rows)
    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    (ARTIFACTS / "fig_dedup_universal.json").write_text(
        json.dumps(result, indent=1))


if __name__ == "__main__":
    run()
