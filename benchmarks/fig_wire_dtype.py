"""Beyond-paper deliverable (DESIGN.md §14): the compressed exchange
swept across wire dtype × node split.

``LuffyConfig.wire_dtype`` ships activation rows across node boundaries
at f32 (identity), bf16 (cast) or f8e4m3 (block-scaled), priced by ONE
function (``repro.comm.dtypes.wire_precision``) that the plan estimate,
the executed ledger and this benchmark all share. The sweep runs the
dryrun ``comm_traffic_ledger`` over dtype × node-split and CHECKS the
two pricing laws the tests pin at execution time:

* **exact byte scaling** — for every dtype and split, every modeled
  byte field equals the f32 ledger's value divided by exactly
  ``wire_precision(d_model, dtype, 4)``: the ledger contract
  ``bytes == flat / (dedup × precision)`` with the dedup factor
  untouched by the wire dtype;
* **monotone modeled step** — the tuned/modeled step time is monotone
  non-increasing from f32 toward fp8 (shipping fewer bytes over the
  same links can never model slower), per split.

Emits CSV rows and ``artifacts/fig_wire_dtype.json``.
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
    from repro.comm import dtypes as wdt
    from repro.config import SHAPES
    from repro.configs import get_config

    cfg = get_config("moe-gpt2")
    dtypes = ["f32", "bf16"] + (["f8e4m3"] if wdt.have_f8() else [])
    rows = []
    result = {"d_model": cfg.d_model, "dtypes": dtypes, "sweep": {}}

    for nodes in (2, 4, 8):
        base = None
        sync_ms = []
        for wd in dtypes:
            t0 = time.perf_counter()
            led = comm_traffic_ledger(cfg, SHAPES["train_4k"],
                                      _fake_mesh(), nodes=nodes,
                                      wire_dtype=wd)
            dt_us = (time.perf_counter() - t0) * 1e6
            prec = wdt.wire_precision(cfg.d_model, wd, 4)
            assert led["wire"]["dtype"] == wd
            assert led["wire"]["precision"] == prec
            if wd == "f32":
                assert prec == 1.0
                base = led
            # exact 1/precision scaling of EVERY modeled byte field,
            # dedup factor untouched: bytes == flat/(dedup × precision)
            for r in led["buckets"]:
                b, b0 = led["buckets"][r], base["buckets"][r]
                for tier in ("flat", "hier"):
                    for f in ("inter_bytes", "intra_bytes"):
                        got, want = b[tier][f], b0[tier][f] / prec
                        assert abs(got - want) <= 1e-9 * max(want, 1.0), (
                            f"nodes={nodes} {wd} {r} {tier}.{f}: "
                            f"{got} != f32/{prec} = {want}")
            assert led["dedup_factor"] == base["dedup_factor"]
            s = led["buckets"]["0.0"]["overlap"]["sync_ms"]
            sync_ms.append(s)
            rows.append((f"wire/{wd}/nodes{nodes}", dt_us,
                         f"precision={prec:.3f} "
                         f"inter={led['buckets']['0.0']['hier']['inter_bytes']:.3g}B "
                         f"sync={s:.3f}ms"))
            result["sweep"].setdefault(str(nodes), {})[wd] = {
                "precision": prec,
                "row_bytes": led["wire"]["row_bytes"],
                "inter_bytes_hier":
                    led["buckets"]["0.0"]["hier"]["inter_bytes"],
                "inter_bytes_flat":
                    led["buckets"]["0.0"]["flat"]["inter_bytes"],
                "sync_ms": s,
            }
        # modeled step monotone non-increasing toward fp8
        for a, b in zip(sync_ms, sync_ms[1:]):
            assert b <= a + 1e-12, (
                f"nodes={nodes}: modeled step must be monotone "
                f"non-increasing toward fp8, got {sync_ms}")

    emit(rows)
    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    (ARTIFACTS / "fig_wire_dtype.json").write_text(
        json.dumps(result, indent=1))


if __name__ == "__main__":
    run()
