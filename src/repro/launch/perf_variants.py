"""§Perf hillclimb variant runner.

Runs the three chosen (arch × shape) pairs under before/after variants
(env flags + condensation buckets), writing variant-tagged artifacts to
artifacts/perf/. EXPERIMENTS.md §Perf is written from these.

    PYTHONPATH=src python -m repro.launch.perf_variants
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
OUT = ROOT / "artifacts" / "perf"

# (arch, shape, variant_name, env, extra_args)
VARIANTS = [
    # H1 — gemma3 prefill_32k: windowed-band chunk skipping
    ("gemma3-12b", "prefill_32k", "band_off",
     {"REPRO_ATTN_BAND": "0"}, []),
    ("gemma3-12b", "prefill_32k", "band_on",
     {"REPRO_ATTN_BAND": "1"}, []),
    # H2 — llama4 decode_32k: Megatron-style 2D expert decode
    ("llama4-maverick-400b-a17b", "decode_32k", "decode2d_off",
     {"REPRO_MOE_DECODE_2D": "0"}, []),
    ("llama4-maverick-400b-a17b", "decode_32k", "decode2d_on",
     {"REPRO_MOE_DECODE_2D": "1"}, []),
    # H3 — olmoe train_4k: condensation capacity buckets (the paper's
    # technique becoming real wire savings) + LUFFY fully off
    ("olmoe-1b-7b", "train_4k", "noluffy", {}, ["--no-luffy"]),
    ("olmoe-1b-7b", "train_4k", "bucket0", {}, ["--bucket", "0"]),
    ("olmoe-1b-7b", "train_4k", "bucket1", {}, ["--bucket", "1"]),
    ("olmoe-1b-7b", "train_4k", "bucket2", {}, ["--bucket", "2"]),
    # H1b — hymba prefill_32k: SSM scan unroll (chunked-scan insight)
    ("hymba-1.5b", "prefill_32k", "unroll1",
     {"REPRO_SSM_UNROLL": "1"}, []),
    ("hymba-1.5b", "prefill_32k", "unroll8",
     {"REPRO_SSM_UNROLL": "8"}, []),
]


def main(jobs: int = 4):
    OUT.mkdir(parents=True, exist_ok=True)
    work = []
    for arch, shape, var, env, extra in VARIANTS:
        out = OUT / f"{arch}__{shape}__{var}.json"
        if out.exists():
            try:
                if json.loads(out.read_text()).get("status") == "ok":
                    continue
            except Exception:
                pass
        work.append((arch, shape, var, env, extra, out))
    print(f"{len(work)} perf-variant jobs")
    procs = []
    while work or procs:
        while work and len(procs) < jobs:
            arch, shape, var, env, extra, out = work.pop(0)
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--out", str(out),
                   "--variant", var] + extra
            full_env = {**os.environ, "PYTHONPATH": "src",
                        "JAX_PLATFORMS": "cpu", **env}
            logf = open(str(out) + ".log", "w")
            procs.append((subprocess.Popen(
                cmd, stdout=logf, stderr=subprocess.STDOUT, env=full_env,
                cwd=str(ROOT)), var, out, logf, time.time()))
            print("launched", arch, shape, var)
        still = []
        for pr, var, out, logf, t0 in procs:
            if pr.poll() is None:
                if time.time() - t0 > 3600:
                    pr.kill()
                else:
                    still.append((pr, var, out, logf, t0))
            else:
                logf.close()
                print(f"done {var} rc={pr.returncode}")
        procs = still
        time.sleep(3)
    print("perf variants complete")


if __name__ == "__main__":
    main()
