"""Readings from which the limits of ``limits/<workload>.json`` are set:
for each seed and each rate bucket that the cell's condensation reaches,
the numbers that ``correct`` compares for the program (three steps of
that bucket's executable against the plain reference), with each leaf's
gap beside them; and at bucket 0's capacity, for the control (the
reference computed with int8 matrix products in the program's place)
and for faults planted in the reference. Each line says whether it
passes the cell's current limits.

    python3 benchmarks/onchip/readings.py --workload gpt2-l4.1chip.zipf \\
        --seeds 1,2,3 --control-seeds 1,2,3 --faults half_batch,token \\
        --out readings.jsonl

One process: the program's steps compile once and serve every seed.
Writes one JSON line per seed, bucket and kind to ``--out`` and prints
them. A step that returns its state unchanged needs no run: it reads 1
on the first gradient and on the change.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
CHECKOUT = ROOT.parents[1]


def ints(s: str):
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=ints, default=[])
    ap.add_argument("--control-seeds", type=ints, default=[])
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", type=ints, default=None)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(CHECKOUT / "src"))
    sys.path.insert(0, str(ROOT))
    import harness
    import traffic as traffic_mod
    from run import setup_jax_cache

    import jax
    cell = harness.resolve(harness.load_spec(), args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print("needs the cell's TPU chips", file=sys.stderr)
        return 2
    setup_jax_cache()
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    prog = harness.Program(cell, devs)
    ref_mod = harness.reference_module(cell.conf)
    rates = prog.luffy.rate_buckets
    faults = [f for f in args.faults.split(",") if f]
    fault_seeds = (args.fault_seeds if args.fault_seeds is not None
                   else args.control_seeds)
    seeds = sorted(set(args.seeds) | set(args.control_seeds)
                   | set(fault_seeds))
    buckets = [0]

    def emit(seed, b, kind, got, ref, **extra):
        nums = harness.numbers(got, ref)
        rec = {"workload": cell.name, "seed": seed, "bucket": b,
               "kind": kind, "numbers": nums,
               "leaf_gaps": harness.leaf_gaps(got, ref),
               "passes": (harness.passed(harness.judge({b: nums},
                                                       cell.limits))
                          if cell.limits else None), **extra}
        line = json.dumps(rec)
        print(line, flush=True)
        with out.open("a") as fh:
            fh.write(line + "\n")

    for seed in seeds:
        t0 = time.perf_counter()
        pool = traffic_mod.make_pool(cell.traffic, prog.cfg.vocab_size,
                                     seed)
        got = {}
        if seed in args.seeds:
            prog.init_state(seed)
            if 0 not in prog.exes:
                prog.compile(pool[0], [0])
            got[0] = harness.check_steps(prog, pool, ref_mod)
            if len(buckets) == 1 and any(r > 0 for r in got[0]["rates"]):
                buckets = list(range(len(rates)))
                prog.compile(pool[0], buckets[1:])
            for b in buckets[1:]:
                prog.free_state()
                prog.init_state(seed)
                got[b] = harness.check_steps(prog, pool, ref_mod, bucket=b)
            prog.free_state()
        for b in (buckets if got else [0]):
            ref = harness.reference_side(cell, seed, pool, rate=rates[b])
            if b in got:
                emit(seed, b, "program", got[b], ref,
                     losses=got[b]["losses"], ref_losses=ref["losses"],
                     rates=got[b]["rates"], max_drop=max(got[b]["drops"]))
            kinds = ([("control", True, None)]
                     if b == 0 and seed in args.control_seeds else [])
            if b == 0 and seed in fault_seeds:
                kinds += [(f, False, f) for f in faults]
            for kind, lower, fault in kinds:
                other = harness.reference_side(cell, seed, pool,
                                               rate=rates[b], lower=lower,
                                               fault=fault)
                emit(seed, b, kind, other, ref,
                     losses=other["losses"], ref_losses=ref["losses"])
        print(f"seed {seed} done in {time.perf_counter() - t0:.1f}s",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
