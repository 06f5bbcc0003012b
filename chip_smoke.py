"""Smoke run of LUFFY-JAX on a TPU: compiled kernels against their
oracles, then a few training steps of MoE-GPT2 at full width through
the train launcher, all in this one process.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # expert parallelism over 4 chips

The last line of standard output is ``{"ok": true, "device": {...}}``;
it is printed only when every phase passed. Without a TPU, or outside
a checkout of the repository, the script exits non-zero and prints no
result.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "moe-gpt2"
VOCAB = 50257
ONE_CHIP_LAYERS = 4      # 12 layers hold ~23 GB of f32 AdamW state
# step 0 of the synthetic stream: 30% of labels repeat the input token,
# which tied embeddings favour at init, so the loss starts below ln V
LOSS0_BELOW, LOSS0_ABOVE = 3.0, 1.0
# f8 payload vs its XLA oracle: both divide by the same f32 scale, but
# two compilers' f32 divisions may differ in the last bit, which moves a
# quotient lying on an f8 rounding boundary to the neighbouring code
F8_MAX_CODE_DIFF = 1e-5
# vanilla vs LUFFY step-0 losses (same parameters, same batch):
# condensation merges similar tokens, so they agree only approximately;
# later steps follow different gradients and are printed, not compared
COND_REL_TOL = 0.02


class SmokeError(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def device_phase(want: int) -> dict:
    import jax
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: JAX runs on {devs[0].platform!r}")
    check(len(devs) >= want, f"need {want} chips, JAX sees {len(devs)}")
    from repro.launch.device import device_banner, enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    return device_banner()


def _rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-30))


def _host_bytes(x):
    """``x`` on the host in C order, viewed as its bytes. A device array
    may reach numpy with a strided layout (the TPU lays out a [R, 1]
    array column-first), which ``view`` refuses to reinterpret."""
    import numpy as np
    return np.ascontiguousarray(np.asarray(x)).view(np.uint8)


def _bits_equal(got, want) -> bool:
    import numpy as np
    got, want = np.asarray(got), np.asarray(want)
    return (got.dtype == want.dtype and got.shape == want.shape
            and np.array_equal(_host_bytes(got), _host_bytes(want)))


def _f8_code_diff(got, want):
    """Count of differing f8 codes, and whether each differs by one code
    (the next value up or down: f8e4m3fn codes of one sign are ordered)."""
    import numpy as np
    g = _host_bytes(got).astype(np.int16)
    w = _host_bytes(want).astype(np.int16)
    diff = g != w
    return int(diff.sum()), bool(np.all(np.abs(g[diff] - w[diff]) == 1))


def _compiled(fn, *args):
    """Run ``fn`` through an ahead-of-time compile and check that a
    Mosaic kernel is in the program."""
    import jax
    exe = jax.jit(fn).lower(*args).compile()
    check("tpu_custom_call" in exe.as_text(),
          f"{getattr(fn, '__name__', fn)}: no Mosaic kernel compiled")
    return jax.block_until_ready(exe(*args))


def kernel_phase() -> None:
    """Each kernel compiled (interpret=False) at MoE-GPT2 widths against
    its ``kernels/ref.py`` oracle."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref, resolve_interpret
    check(resolve_interpret() is False, "kernels would run interpreted")
    d, F, E, R, T, G = 768, 3072, 16, 512, 8192, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    bf = jnp.bfloat16

    h = jax.random.normal(ks[0], (E, R, d), bf)
    wu = (jax.random.normal(ks[1], (E, d, F)) / math.sqrt(d)).astype(bf)
    wg = (jax.random.normal(ks[2], (E, d, F)) / math.sqrt(d)).astype(bf)
    wd = (jax.random.normal(ks[3], (E, F, d)) / math.sqrt(F)).astype(bf)
    got = _compiled(lambda *a: ops.expert_ffn(*a, "gelu"), h, wu, wg, wd)
    with jax.default_matmul_precision("highest"):
        want = ref.expert_ffn_ref(h, wu, wg, wd, "gelu")
    err = _rel_err(got, want)
    print(f"kernel expert_ffn E={E} R={R} d={d} F={F} bf16: "
          f"max rel err {err}")
    check(err < 2e-2, f"expert_ffn off its oracle by {err}")

    x = jax.random.normal(ks[4], (G, d), bf)
    e = jax.random.randint(ks[5], (G,), 0, 4)
    mask = e[:, None] == e[None, :]
    got = _compiled(ops.masked_similarity, x, mask)
    with jax.default_matmul_precision("highest"):
        want = ref.masked_similarity_ref(x, mask)
    err = _rel_err(got, want)
    print(f"kernel masked_similarity G={G} d={d} bf16: max rel err {err}")
    check(err < 2e-2, f"masked_similarity off its oracle by {err}")

    for dt in (jnp.float32, bf):
        y = jax.random.normal(ks[6], (T, d), dt)
        idx = jax.random.randint(ks[7], (T,), 0, T)
        ok = _bits_equal(_compiled(ops.gather_rows, y, idx),
                         ref.gather_rows_ref(y, idx))
        print(f"kernel gather_rows T={T} d={d} {jnp.dtype(dt).name}: "
              f"bitwise {ok}")
        check(ok, "gather_rows differs from its oracle")

    x = jax.random.normal(ks[6], (T, d), bf)
    tok = jax.random.randint(ks[7], (T,), -1, T)
    got_q, got_s = _compiled(
        lambda a, b: ops.pack_quantize(a, b, wire_dtype="bf16"), x, tok)
    want_q, _ = ref.pack_quantize_ref(x, tok, wire_dtype="bf16")
    ok = got_s is None and _bits_equal(got_q, want_q)
    print(f"kernel pack_quantize R={T} d={d} wire=bf16: bitwise {ok}")
    check(ok, "pack_quantize bf16 differs from its oracle")

    got_q, got_s = _compiled(
        lambda a, b: ops.pack_quantize(a, b, wire_dtype="f8e4m3"), x, tok)
    want_q, want_s = ref.pack_quantize_ref(x, tok, wire_dtype="f8e4m3")
    check(_bits_equal(got_s, want_s), "pack_quantize f8 scales differ")
    n, adjacent = _f8_code_diff(got_q, want_q)
    print(f"kernel pack_quantize R={T} d={d} wire=f8e4m3: scales bitwise "
          f"True, payload bitwise {n == 0} ({n} of {got_q.size} codes "
          f"differ, all by one code: {adjacent})")
    check(adjacent and n <= F8_MAX_CODE_DIFF * got_q.size,
          "pack_quantize f8 payload differs from its oracle")


def _train(argv) -> dict:
    from repro.launch import train
    gc.collect()     # free the previous run's device arrays first
    print("train:", " ".join(argv), flush=True)
    run = train.main(argv)
    losses = run["losses"]
    check(all(math.isfinite(x) for x in losses),
          f"non-finite loss in {losses}")
    print(f"  losses {losses}")
    print(f"  step_s {run['step_s']}")
    print(f"  compile_s {run['compile_s']}")
    print(f"  peak_bytes_in_use {run['peak_bytes_in_use']}")
    return run


def train_phase() -> None:
    """Full-width MoE-GPT2, depth cut to fit one chip, LUFFY on."""
    steps = 5
    run = _train(["--arch", ARCH, "--layers", str(ONE_CHIP_LAYERS),
                  "--seq-len", "1024", "--global-batch", "8",
                  "--steps", str(steps), "--optimizer", "adamw",
                  "--mesh", "none"])
    check(run["layers"] == ONE_CHIP_LAYERS, "depth cut not applied")
    check(len(run["losses"]) == steps, "missing steps")
    loss0, lnv = run["losses"][0], math.log(VOCAB)
    check(lnv - LOSS0_BELOW < loss0 < lnv + LOSS0_ABOVE,
          f"step-0 loss {loss0} is not near ln({VOCAB}) = {lnv}")


def four_chip_phase() -> None:
    """All 12 layers, experts sharded 4 per chip: (a) vanilla expert
    parallelism on a (data=1, model=4) mesh against LUFFY with (b) flat
    and (c) hierarchical collectives. (b) and (c) share the (node=2,
    local=2) mesh and its topology, so the migration planner sees the
    same link costs and the two runs differ only in the collectives."""
    common = ["--arch", ARCH, "--seq-len", "1024", "--global-batch", "16",
              "--steps", "3", "--optimizer", "adamw", "--mesh", "host",
              "--model-axis", "4"]
    a = _train(common + ["--no-migration", "--no-condensation"])
    b = _train(common + ["--comm-mode", "flat", "--nodes", "2"])
    c = _train(common + ["--comm-mode", "hier", "--nodes", "2"])
    for run in (a, b, c):
        check(run["layers"] == 12, "four-chip run must be all 12 layers")
        check(run["expert_shard"][-3] == 16 // 4,
              f"experts not 4 per chip: shard {run['expert_shard']}")
    check(b["losses"] == c["losses"],
          f"flat {b['losses']} != hier {c['losses']}")
    print(f"flat == hier losses: {b['losses']}")
    diffs = [abs(x - y) / abs(x) for x, y in zip(a["losses"], b["losses"])]
    print(f"vanilla vs LUFFY rel loss diff per step {diffs} "
          f"(step-0 tolerance {COND_REL_TOL})")
    check(diffs[0] < COND_REL_TOL, "vanilla and LUFFY step-0 losses "
          "disagree")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the expert-parallel path on 4 chips")
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repository source at {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    t0 = time.perf_counter()
    try:
        device = device_phase(4 if args.four_chips else 1)
        if args.four_chips:
            four_chip_phase()
        else:
            kernel_phase()
            train_phase()
    except SmokeError as e:
        print(f"chip_smoke: FAIL {e}", file=sys.stderr)
        return 1
    print(f"chip_smoke: all phases passed in {time.perf_counter() - t0}s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
