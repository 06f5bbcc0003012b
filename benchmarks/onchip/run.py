"""On-chip training benchmark: one cell of ``BENCHMARK.json``.

    python3 benchmarks/onchip/run.py --workload gpt2-l4.1chip.zipf \
        --seed 7 --seconds 20 --trace 0

Builds the cell's model through the program's library, makes weights
and a pool of batches from ``--seed``, compiles every condensation rate
bucket and runs three check steps through each bucket's step that the
cell's condensation reaches (set-up), then trains for ``--seconds``
through the launcher's host loop. With ``--trace 1`` the window is
traced (at most 8 s) and the per-layer metrics are reported instead of
the end-to-end ones, with device ms a step per scope class on a
``scopes {...}`` line. Afterwards the plain float32 reference follows each
bucket's three check steps from the same weights and batches;
``correct`` says whether the program stayed within each limit of
``limits/<workload>.json``.

Prints, on standard output, lines with the set-up time, the number of
compiles in the window and the model FLOPs a token, the losses of the
check steps, the window in quarters (steps, step time, rate buckets,
condensation rate), the memory readings and, traced, the scope classes,
then one JSON result line; on standard error, last,
each number compared beside its limit. Exits non-zero, with no result,
where JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent
CHECKOUT = ROOT.parents[1]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def setup_jax_cache():
    """JAX's persistent compilation cache: where the environment says,
    else at a fixed directory inside the checkout; every program is
    cached, however quick its compile."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(CHECKOUT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def report(out: dict) -> None:
    info = out.pop("_info")
    print(f"setup_s={info['setup_s']} compiles_in_window="
          f"{info['compiles_in_window']} steps={info['steps']} "
          f"window_s={info['window_s']} buckets={info['buckets']} "
          f"max_drop={info['max_drop']} "
          f"flops_per_token={info['flops_per_token']}", flush=True)
    print(f"losses program={info['program_losses']} "
          f"reference={info['reference_losses']}", flush=True)
    print(f"timeline {json.dumps(info['timeline'])}", flush=True)
    print(f"memory {json.dumps(info['memory'])}", flush=True)
    if "scopes" in info:
        print(f"scopes {json.dumps(info['scopes'])}", flush=True)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    args = parse(argv)
    src = CHECKOUT / "src"
    if not (src / "repro").is_dir():
        print(f"no program under {src}", file=sys.stderr)
        return 3
    sys.path.insert(0, str(src))
    sys.path.insert(0, str(ROOT))
    import harness
    cell = harness.resolve(harness.load_spec(), args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"needs {cell.chips} TPU chip(s); JAX sees {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        return 2
    setup_jax_cache()
    if args.trace:
        # the scope classes are read from the executables' op_name
        # metadata; a cache key without it could hand this program an
        # executable compiled from the same code under other scopes, or
        # none
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
    counter = harness.CompileCounter()
    prog = harness.Program(cell, devs)
    trace_dir = Path(tempfile.mkdtemp(prefix="onchip-trace-")) \
        if args.trace else None
    try:
        out = harness.run_cell(prog, args.seed, args.seconds,
                               bool(args.trace), T_START, counter,
                               trace_dir=trace_dir)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    report(out)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # skip interpreter teardown, which may log after the limit lines
    os._exit(rc)
