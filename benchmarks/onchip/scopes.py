"""Device time per layer of the train step, from the named scopes the
program puts on its operations: inside ``jit``, ``repro.obs.trace.phase``
is ``jax.named_scope``, so each HLO instruction of the compiled step
carries its scope path in its ``op_name`` metadata (forward, backward
and rematerialised operations alike).

Each leaf device operation of the traced window (as
``tracereduce.load`` returns them) is looked up by its HLO instruction
name in the ``op_name`` map of the executable that ran it: that of the
rate bucket of the host ``step`` span the operation falls in. Its
``op_name`` path gives its class, the first of the classes (``CLASSES``,
after those an architecture's layout adds, ``SCOPE_CLASSES``) that names
one of its scopes; an operation that is not found, or whose path names
none, is ``other``. ``harness.run_cell`` puts the result of a traced
run under ``"scopes"`` in the reduced trace, where the readers
``metrics/<class>_ms.py`` find it (``class_ms``).

``attribute`` works on those records and maps alone, so it is tested on
a hand-made trace and on a recorded chip trace.

Run on the chip, for one cell of ``BENCHMARK.json``, it runs the cell
traced as ``run.py --trace 1`` does and prints a ``scopes {...}`` line,
ms per step per class with the found share and each class's longest
operations, before the harness's own lines:

    python3 benchmarks/onchip/scopes.py --workload gpt2-l4.1chip.zipf \
        --seed 7 --seconds 8
"""
from __future__ import annotations

import bisect
import json
import os
import re
import sys
import time
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

# class -> the scopes (``phase`` names) it holds; first match wins
Classes = Tuple[Tuple[str, Tuple[str, ...]], ...]
CLASSES: Classes = (
    ("optimizer", ("optimizer",)),
    ("expert_ffn", ("expert_ffn",)),
    ("dispatch_combine", ("dispatch", "dispatch_pack", "combine",
                          "combine_unpack")),
    ("moe_plan", ("router", "plan_build", "condense")),
    ("attention", ("attention",)),
    ("lm_head", ("embed", "lm_head")),
)
OTHER = "other"
NAMES = tuple(c for c, _ in CLASSES) + (OTHER,)
TOP = 3                  # operations listed per class

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ", re.M)
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_INDEX = re.compile(r"\[[^\]]*\]")      # params['embed']: a path, no scope


def op_names(hlo_text: str) -> Dict[str, str]:
    """HLO instruction name -> its ``op_name`` ("" where it has none),
    for every instruction of a compiled module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            n = _OP_NAME.search(line, m.end())
            out[m.group(1)] = n.group(1) if n else ""
    return out


def words(op_name: str) -> set:
    """The names on an ``op_name`` path: ``jit(step)/transpose(jvp(
    lm_head))/while/body/dot_general`` holds ``lm_head``. Indexing
    (``params['embed']``, an argument's path) names no scope."""
    return set(re.findall(r"\w+", _INDEX.sub("", op_name)))


def classify(op_name: str, classes: Classes = CLASSES) -> str:
    w = words(op_name)
    for cls, scopes in classes:
        if w.intersection(scopes):
            return cls
    return OTHER


def scopes_seen(maps: Dict[int, Dict[str, str]],
                classes: Classes = CLASSES) -> List[str]:
    """The scopes of ``classes`` that any instruction of the maps is
    under: a program without named scopes has none."""
    every = {s for _, ss in classes for s in ss}
    seen = set()
    for m in maps.values():
        for op in set(m.values()):
            seen |= words(op) & every
    return sorted(seen)


def attribute(rec: dict, window: Tuple[int, int],
              maps: Dict[int, Dict[str, str]], buckets: List[int],
              classes: Classes = CLASSES) -> dict:
    """Device time per class in ``window``, per step and mean over the
    chips. ``rec`` is ``tracereduce.load``'s records, ``maps`` each rate
    bucket's ``op_names``, ``buckets`` the bucket of each step of the
    window in order (the i-th host ``step`` span ran ``buckets[i]``),
    ``classes`` the classes in the order they are matched.

    Returns ms per step per class, the leaf operations' summed time per
    step (the classes add up to it), the share of it whose operation was
    found in its map, the scopes the maps hold, and the ``TOP`` longest
    operations of each class with their ``op_name``."""
    lo, hi = window
    spans = sorted((int(s), int(e)) for s, e, n in rec["host"]
                   if n == "step" and int(e) > lo and int(s) < hi)
    starts = [s for s, _ in spans]
    # a step span that cannot be paired with a bucket finds nothing
    paired = len(spans) == len(buckets)
    n_dev = max(len(rec["devices"]), 1)
    t_cls: Dict[str, float] = defaultdict(float)
    t_op: Dict[Tuple[str, str, str], float] = defaultdict(float)
    found = total = 0.0
    for ops in rec["devices"].values():
        for s, e, label, leaf in ops:
            s, e = int(s), int(e)
            t = (min(e, hi) - max(s, lo)) / 1e9
            if not leaf or t <= 0:
                continue
            total += t
            name = label.split(" ")[0]
            op = None
            i = bisect.bisect_right(starts, s) - 1
            if paired and i >= 0 and s < spans[i][1]:
                op = maps.get(buckets[i], {}).get(name)
            if op is not None:
                found += t
            cls = OTHER if op is None else classify(op, classes)
            t_cls[cls] += t
            t_op[(cls, label, op or "")] += t
    n = max(len(spans), 1)
    per_step = 1e3 / n_dev / n
    names = tuple(dict.fromkeys(c for c, _ in classes)) + (OTHER,)
    top = {c: [] for c in names}
    for (cls, label, op), t in sorted(t_op.items(), key=lambda kv: -kv[1]):
        if len(top[cls]) < TOP:
            top[cls].append([label, op, t * per_step])
    return {"source": "hlo_op_name", "steps": len(spans),
            "ms": {c: t_cls[c] * per_step for c in names},
            "leaf_ms": total * per_step,
            "found_share": 100.0 * found / total if total else 0.0,
            "scopes_seen": scopes_seen(maps, classes), "top": top}


def of_run(rec: dict, window: Tuple[int, int], exes: dict,
           steps: List[dict], classes: Classes = CLASSES) -> dict:
    """``attribute`` for a traced run: the maps from the compiled
    executables of the buckets the window ran (``exe.as_text()``). Adds
    the seconds this took."""
    t0 = time.perf_counter()
    buckets = [s["bucket"] for s in steps]
    maps = {b: op_names(exes[b].as_text()) for b in sorted(set(buckets))}
    out = attribute(rec, window, maps, buckets, classes)
    out["seconds"] = time.perf_counter() - t0
    return out


def class_ms(rec, cls: str, classes: Classes = CLASSES) -> Optional[float]:
    """A per-layer reader's value: device ms a step of scope class
    ``cls`` of ``classes`` in a traced run (``rec.trace["scopes"]``), or
    None where the run is not traced or its program carries none of the
    class's scopes (a program that names no scope puts all its time in
    ``other``)."""
    if rec.trace is None or "scopes" not in rec.trace:
        return None
    sc = rec.trace["scopes"]
    own = {s for c, ss in classes if c == cls for s in ss}
    if not own.intersection(sc["scopes_seen"]):
        return None
    return sc["ms"][cls]


def recording(prog) -> List[dict]:
    """Record every step ``prog`` takes from now on, in order: the list
    it returns grows by each ``prog.step`` result."""
    taken: List[dict] = []
    step = prog.step

    def recorded(*args, **kw):
        taken.append(step(*args, **kw))
        return taken[-1]

    prog.step = recorded
    return taken


def run_traced(prog, seed: int, seconds: float, t_start: float, counter,
               trace_dir) -> Tuple[dict, dict]:
    """``harness.run_cell`` traced into ``trace_dir``, and ``of_run`` on
    its window, whose steps are the last ``attempted`` the program
    took."""
    import harness
    import tracereduce
    taken = recording(prog)
    out = harness.run_cell(prog, seed, seconds, True, t_start, counter,
                           trace_dir=trace_dir)
    recs = tracereduce.load(trace_dir)
    return out, of_run(recs, tracereduce.window_of(recs), prog.exes,
                       taken[len(taken) - out["attempted"]:])


def main(argv=None) -> int:
    import argparse
    import shutil
    import tempfile
    from pathlib import Path
    import run                                  # its clock starts set-up
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(run.CHECKOUT / "src"))
    import harness
    cell = harness.resolve(harness.load_spec(), args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"needs {cell.chips} TPU chip(s); JAX sees {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        return 2
    run.setup_jax_cache()
    # the maps are read from the executables' op_name metadata; a cache
    # key without it could hand this program an executable compiled from
    # the same code under other scopes, or none (stale metadata)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    counter = harness.CompileCounter()
    prog = harness.Program(cell, devs)
    trace_dir = Path(tempfile.mkdtemp(prefix="onchip-scopes-"))
    try:
        out, res = run_traced(prog, args.seed, args.seconds, run.T_START,
                              counter, trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    print(f"scopes {json.dumps(res)}", flush=True)
    run.report(out)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
