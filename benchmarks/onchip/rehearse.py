"""Compile rehearsal, without a chip: every rate bucket's training step
of a cell, and the reference's gradient and update programs, compiled
at the cell's real sizes for a described TPU v5e (one chip, or the
v5e:2x2 mesh for four), printing each program's memory analysis.

    JAX_PLATFORMS=cpu PYTHONPATH=src \
        python3 benchmarks/onchip/rehearse.py --workload gpt2-l4.1chip.zipf

Nothing runs, so this says nothing of times or results; it finds a
program the chip's compiler refuses or that does not fit its memory.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
CHECKOUT = ROOT.parents[1]
GIB = 2 ** 30


def mem(exe) -> str:
    ma = exe.memory_analysis()
    if ma is None:
        return "no memory analysis"
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    return (f"argument={ma.argument_size_in_bytes / GIB:.3f}GiB "
            f"output={ma.output_size_in_bytes / GIB:.3f}GiB "
            f"alias={ma.alias_size_in_bytes / GIB:.3f}GiB "
            f"temp={ma.temp_size_in_bytes / GIB:.3f}GiB "
            f"total={total / GIB:.3f}GiB")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batch", type=int, default=0,
                    help="try another global batch than the traffic's")
    ap.add_argument("--layers", type=int, default=0,
                    help="try another depth than the configuration's")
    ap.add_argument("--skip-reference", action="store_true")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(CHECKOUT / "src"))
    sys.path.insert(0, str(ROOT))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    import harness
    from repro import optim, train_lib

    jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.resolve(harness.load_spec(), args.workload)
    if args.batch:
        cell.traffic = dict(cell.traffic, global_batch=args.batch)
    if args.layers:
        cell.conf = dict(cell.conf, num_layers=args.layers)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    prog = harness.Program(cell, topo.devices)

    def sds(shape, dtype, sh):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    params = jax.tree.map(lambda s, sh: sds(s.shape, s.dtype, sh),
                          prog.struct, prog.param_sh)
    f32 = jax.tree.map(lambda s, sh: sds(s.shape, jnp.float32, sh),
                       prog.struct, prog.param_sh)
    opt = optim.OptState(sds((), jnp.int32, prog.repl), f32, f32)
    lst = train_lib.LuffyState(sds((), jnp.float32, prog.repl),
                               sds((), jnp.float32, prog.repl),
                               sds((), jnp.int32, prog.repl), None)
    B, S = prog.B, prog.S
    batch = {"tokens": sds((B, S), jnp.int32, prog.batch_sh["tokens"]),
             "labels": sds((B, S), jnp.int32, prog.batch_sh["labels"]),
             "seq_len": sds((B,), jnp.int32, prog.batch_sh["seq_len"])}
    times = prog.compile(None, lower_only_args=(params, opt, lst, batch))
    for b, exe in prog.exes.items():
        text = exe.as_text()
        print(f"{cell.name} step bucket={b} compile={times[b]:.1f}s "
              f"{mem(exe)} all-to-all={text.count(' all-to-all(')} "
              f"per chip", flush=True)
    if args.skip_reference:
        return 0
    ref = harness.reference_module(cell.conf)
    one = SingleDeviceSharding(topo.devices[0])
    canon = {n: sds(s, jnp.float32, one) for n, (s, _, _)
             in harness.layout_module(cell.conf).shapes(cell.conf).items()}
    vg, update = ref.step_fns(cell.conf, harness.JOB_OPT, group=min(128, S))
    scal = sds((), jnp.float32, one)
    t0 = time.perf_counter()
    ints = jnp.int32
    exe = vg.lower(canon, sds((B, S), ints, one), sds((B, S), ints, one),
                   sds((B,), ints, one), scal).compile()
    print(f"{cell.name} reference vg rows={B} "
          f"compile={time.perf_counter() - t0:.1f}s {mem(exe)}", flush=True)
    exe = update.lower(canon, canon, canon, canon, scal, scal).compile()
    print(f"{cell.name} reference update {mem(exe)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
