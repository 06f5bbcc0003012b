"""End-to-end driver example: expert-parallel MoE training on an 8-way
host mesh (2 data x 4 model), with both LUFFY techniques and the
rate-bucket recompile loop — a scaled-down copy of the production path.

    python examples/expert_parallel_training.py [--steps 100]

(Spawns itself with XLA_FLAGS for 8 host devices.)
"""
import os
import subprocess
import sys

if os.environ.get("_EP_CHILD") != "1":
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"            # 8 host devices, never the chip
    env["_EP_CHILD"] = "1"
    env.setdefault("PYTHONPATH", "src")
    raise SystemExit(subprocess.call(
        [sys.executable, "-m", "repro.launch.train",
         "--arch", "moe-transformerxl", "--reduced", "--experts", "8",
         "--d-model", "256", "--layers", "2", "--global-batch", "16",
         "--seq-len", "256", "--mesh", "host", "--model-axis", "4",
         "--steps", (sys.argv[sys.argv.index("--steps") + 1]
                     if "--steps" in sys.argv else "60")],
        env=env))
