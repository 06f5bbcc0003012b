"""Paper Fig. 9 — ablation: condensation-only vs migration-only vs full
LUFFY. The LUFFY inputs (condensation rate, migration locality gain) are
MEASURED on this system (8-host-device training, aux ledger), then fed to
the Table-III-calibrated comm model to get speedups comparable with the
paper's figure.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from benchmarks.common import ROOT, emit
from repro.configs import get_config
from repro.core import commsim

_MEASURE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from repro import optim, train_lib
from repro.config import reduced, LuffyConfig, OptimConfig, ShapeConfig
from repro.configs import get_config
from repro.core.moe_layer import capacity_for
from repro.data import SyntheticLM
from repro.dist import DistContext
from repro.models.model import build_model

cfg = reduced(get_config("moe-transformerxl", num_experts=8),
              num_layers=2, d_model=128, max_experts=8)
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
shape = ShapeConfig("b", 256, 8, "train")
data = SyntheticLM(cfg, shape)
from repro.comm import make_mesh
mesh = make_mesh((1, 8), ("data", "model"))
dist = DistContext(mesh, batch_axes=("data", "model"), seq_axis=None,
                   fsdp_axes=("data",))
luffy = LuffyConfig(condense_group=64, combine_slack=2.0)
cap = capacity_for(cfg.moe, 256, cfg.moe.num_experts)
ocfg = OptimConfig(total_steps=%(steps)d, warmup_steps=2, lr=1e-3)
step = jax.jit(train_lib.make_train_step(cfg, luffy, ocfg, dist, cap))
ost = optim.init_opt_state(params, ocfg)
lst = train_lib.init_luffy_state()
rates, locals_, tb, ta = [], [], [], []
for i in range(%(steps)d):
    b = {k: jnp.asarray(v) for k, v in data.batch(i).items()}
    params, ost, lst, m = step(params, ost, lst, b)
    rates.append(float(m["condense_rate"]))
    locals_.append(float(m["local_frac"]))
    tb.append(float(m["traffic_before"])); ta.append(float(m["traffic_after"]))
n = max(1, len(rates) // 2)
r = sum(rates[-n:]) / n
lf = sum(locals_[-n:]) / n
base_local = 1.0 / 8
loc_gain = max(0.0, (lf - base_local) / max(1e-9, 1.0 - base_local))
tr = 1.0 - (sum(ta[-n:]) / max(1e-9, sum(tb[-n:])))
print(json.dumps({"r_cond": r, "local_frac": lf,
                  "locality_gain": loc_gain, "traffic_reduction": tr}))
"""


def measure(steps: int = 8):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env["JAX_PLATFORMS"] = "cpu"            # 8 host devices, never the chip
    out = subprocess.run([sys.executable, "-c", _MEASURE % {"steps": steps}],
                         capture_output=True, text=True, env=env,
                         cwd=str(ROOT), timeout=1800)
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-2000:])
    return json.loads(out.stdout.strip().splitlines()[-1])


def run(fast: bool = True):
    m = measure(steps=6 if fast else 20)
    rows = [("fig9/measured", 0.0,
             f"r_cond={m['r_cond']:.2f} local_frac={m['local_frac']:.2f} "
             f"traffic_reduction={m['traffic_reduction']:.2f}")]
    for model in commsim.PAPER_VANILLA:
        cfg = get_config(model, num_experts=8)
        setup = commsim.PaperSetup(cfg=cfg)
        vc, vm = commsim.PAPER_VANILLA[model][8]
        cal = commsim.calibrate(setup, vc, vm)
        base = commsim.predict(setup, cal, system="vanilla")
        bt = base["comp_ms"] + base["comm_ms"]
        variants = {
            "tc_only": {"r_cond": m["r_cond"], "locality": 0.0},
            "sm_only": {"r_cond": 0.0,
                        "locality": max(m["traffic_reduction"], 0.0)},
            "full": {"r_cond": m["r_cond"],
                     "locality": max(m["traffic_reduction"], 0.0)},
        }
        for name, rates in variants.items():
            p = commsim.predict(setup, cal, system="luffy", **rates)
            sp = bt / (p["comp_ms"] + p["comm_ms"])
            rows.append((f"fig9/{model}/{name}", 0.0,
                         f"speedup={sp:.2f}x"))
    emit(rows)
    return rows


if __name__ == "__main__":
    run()
