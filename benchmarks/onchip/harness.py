"""The on-chip training benchmark: set-up, measured window, trace
reduction and the comparison with the plain reference that decides
``correct``. ``run.py`` is the command; this module holds the parts that
the tests, the limit readings (``readings.py``) and the compile
rehearsal (``rehearse.py``) share.

Everything particular to a configuration, a traffic mix or a per-layer
metric lives in a file of its own, found by the name that
``BENCHMARK.json`` gives: ``configs/<config>.json`` (sizes, with the
architecture it names under ``reference``), ``traffic/<traffic>.json``,
``metrics/<metric>.py`` and ``limits/<workload>.json``. An architecture
is two modules of that name: ``reference/<name>.py``, its plain
reference, and ``layout/<name>.py``, what the harness needs to know of
it (``layout_module``).
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import statistics
import sys
import time
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Optional

ROOT = Path(__file__).resolve().parent
CHECKOUT = ROOT.parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import traffic as traffic_mod  # noqa: E402  (benchmark module)
import weights  # noqa: E402

# The training job every cell runs: the launcher's learning rate and the
# optimizer defaults of the program's OptimConfig.
JOB_OPT = {"lr": 1e-3, "warmup_steps": 100, "total_steps": 10_000,
           "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
           "grad_clip": 1.0}
CHECK_STEPS = 3          # steps the reference follows
TRACE_CAP_S = 8.0        # longest traced window (traces are large)
NUMBERS = ("loss_step1", "loss_step2", "loss_step3", "grad1",
           "grad1_median", "change")
NOT_FINITE = 1e30        # what a number that is not finite reads as


# ---------------------------------------------------------------------------
# the cell, from BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    limits: Optional[dict]


def load_spec(checkout: Path = CHECKOUT) -> dict:
    return json.loads((checkout / "BENCHMARK.json").read_text())


def load_config(spec: dict, name: str, checkout: Path = CHECKOUT) -> dict:
    entry = next(c for c in spec["configs"] if c["name"] == name)
    return json.loads((checkout / entry["file"]).read_text())


def resolve(spec: dict, workload: str, checkout: Path = CHECKOUT) -> Cell:
    """Everything one cell needs, by the names BENCHMARK.json gives."""
    try:
        w = next(w for w in spec["workloads"] if w["name"] == workload)
    except StopIteration:
        raise SystemExit(f"unknown workload {workload!r}") from None
    conf = load_config(spec, w["config"], checkout)
    traffic = traffic_mod.load(ROOT / "traffic" / f"{w['traffic']}.json")

    def applies(m):
        if "workloads" in m:
            return workload in m["workloads"]
        return True

    e2e = [m for m in spec["end_to_end"] if applies(m)]
    moves = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if m["moves"] in moves and applies(m)]
    for m in per_layer:
        if not (ROOT / "metrics" / f"{m['name']}.py").is_file():
            raise SystemExit(f"no reader metrics/{m['name']}.py")
    lim_path = ROOT / "limits" / f"{workload}.json"
    limits = json.loads(lim_path.read_text()) if lim_path.is_file() else None
    return Cell(workload, int(w["chips"]), conf, traffic, e2e, per_layer,
                limits)


def load_module(path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        f"onchip_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_module(conf: dict) -> ModuleType:
    return load_module(ROOT / "reference" / f"{conf['reference']}.py")


def layout_module(conf: dict) -> ModuleType:
    """The architecture's layout: ``SIZE_KEYS``, ``program_sizes(base)``
    and ``program_config(base, conf)``; ``shapes(conf)`` and
    ``PROGRAM_PATHS`` (``weights.py``); ``forward_flops_per_token``
    (``flops.py``); optionally ``SCOPE_CLASSES``, scope classes matched
    before ``scopes.CLASSES``."""
    return load_module(ROOT / "layout" / f"{conf['reference']}.py")


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------

def program_config(conf: dict):
    """The program's ModelConfig for this configuration file: the
    registry's architecture with the file's sizes, as the layout places
    them. A size that differs from the registry and is not listed in
    ``reduced`` is an error."""
    from repro.configs import get_config
    layout = layout_module(conf)
    base = get_config(conf["arch"])
    now = layout.program_sizes(base)
    changed = sorted(k for k in layout.SIZE_KEYS if conf[k] != now[k])
    unlisted = [k for k in changed if k not in conf["reduced"]]
    if unlisted:
        raise ValueError(f"{conf['arch']}: {unlisted} differ from the "
                         f"program's configuration and are not in reduced")
    return layout.program_config(base, conf)


class Program:
    """The system under test for one cell: model, mesh, LUFFY defaults,
    optimizer, one compiled step per condensation rate bucket, and the
    state those steps carry. Built through the library calls that
    ``repro.launch.train`` makes."""

    def __init__(self, cell: Cell, devices):
        from jax.sharding import PartitionSpec as P
        from repro.config import LuffyConfig, OptimConfig, ShapeConfig
        from repro.dist import make_dist, single_device
        from repro.launch.mesh import topology_for_mesh
        from repro.comm import make_mesh
        from repro.models.model import build_model
        import jax

        self.cell = cell
        t = cell.traffic
        self.S, self.B = t["seq_len"], t["global_batch"]
        self.layout = layout_module(cell.conf)
        self.cfg = program_config(cell.conf)
        self.shape = ShapeConfig("train", self.S, self.B, "train")
        self.devices = list(devices)[:cell.chips]
        if cell.chips == 1:
            self.dist = single_device()
        else:
            mesh = make_mesh((1, cell.chips), ("data", "model"),
                             devices=self.devices)
            self.dist = make_dist(mesh, "train", self.B, moe_arch=True,
                                  topology=topology_for_mesh(mesh))
        # LUFFY as a user gets it from the launcher's defaults
        self.luffy = LuffyConfig(enable_condensation=True,
                                 enable_migration=True,
                                 condense_group=min(128, self.S),
                                 combine_slack=2.0)
        self.ocfg = OptimConfig(
            name="adamw", lr=JOB_OPT["lr"], b1=JOB_OPT["b1"],
            b2=JOB_OPT["b2"], eps=JOB_OPT["eps"],
            weight_decay=JOB_OPT["weight_decay"],
            grad_clip=JOB_OPT["grad_clip"],
            warmup_steps=JOB_OPT["warmup_steps"],
            total_steps=JOB_OPT["total_steps"])
        self.model = build_model(self.cfg)
        self.struct = self.model.init_struct()
        weights.check_layout(self.layout, cell.conf, self.struct)
        self.pspecs = self.model.param_pspecs(self.dist, self.struct)
        if self.dist.enabled:
            self.param_sh = jax.tree.map(self.dist.sharding, self.pspecs)
            self.batch_sh = {k: s.sharding for k, s in
                             self.model.input_specs(self.shape,
                                                    self.dist).items()}
            self.repl = self.dist.sharding(P())
        else:
            one = jax.sharding.SingleDeviceSharding(self.devices[0])
            self.param_sh = jax.tree.map(lambda _: one, self.struct)
            self.batch_sh = {k: one for k in ("tokens", "labels", "seq_len")}
            self.repl = one
        self.exes: Dict[int, Any] = {}

    # -- state ----------------------------------------------------------------
    def init_state(self, seed: int):
        """Weights, optimizer state and LUFFY state on the device, each
        in one jitted call, weights from ``seed``."""
        import jax
        from repro import optim, train_lib
        conf, layout = self.cell.conf, self.layout
        key = weights.seed_key(seed)
        self.params = jax.jit(
            lambda k: weights.to_program(
                layout, weights.canonical(layout, conf, k)),
            out_shardings=self.param_sh)(key)
        opt_sh = optim.OptState(self.repl, self.param_sh, self.param_sh)
        self.opt = jax.jit(lambda p: optim.init_opt_state(p, self.ocfg),
                           out_shardings=opt_sh)(self.params)
        self.lstate = jax.device_put(train_lib.init_luffy_state(), self.repl)
        self.key = key
        self.observed_rate = 0.0
        self.bucket = 0
        self.i = 0

    def free_state(self):
        for name in ("params", "opt", "lstate"):
            if hasattr(self, name):
                delattr(self, name)
        gc.collect()

    def upload(self, batch_np):
        import jax
        return {k: jax.device_put(v, self.batch_sh[k])
                for k, v in batch_np.items()}

    # -- compile --------------------------------------------------------------
    def compile(self, batch_np, buckets=None, lower_only_args=None
                ) -> List[float]:
        """One executable per rate bucket (all by default), compiled
        ahead of the window with params and optimizer state donated, as
        the launcher does."""
        import jax
        from repro import train_lib
        times = []
        args = lower_only_args or (self.params, self.opt, self.lstate,
                                   self.upload(batch_np))
        if buckets is None:
            buckets = range(len(self.luffy.rate_buckets))
        for b in buckets:
            cap = train_lib.capacity_for_bucket(self.cfg, self.shape,
                                                self.dist, self.luffy, b)
            fn = train_lib.make_train_step(self.cfg, self.luffy, self.ocfg,
                                           self.dist, cap,
                                           param_pspecs=self.pspecs)
            t0 = time.perf_counter()
            self.exes[b] = jax.jit(fn, donate_argnums=(0, 1)).lower(
                *args).compile()
            times.append(time.perf_counter() - t0)
        return times

    # -- one step of the launcher's host loop ---------------------------------
    def step(self, batch_np, bucket: Optional[int] = None) -> dict:
        """Upload, run and wait, pull the step's metrics, choose the
        next bucket: ``repro.launch.train``'s loop body. ``bucket``
        forces this step's bucket (warm-up of every executable)."""
        import jax
        from jax.profiler import TraceAnnotation
        from repro import train_lib
        b = self.bucket if bucket is None else bucket
        t0 = time.perf_counter()
        with TraceAnnotation("upload"):
            batch = self.upload(batch_np)
        t1 = time.perf_counter()
        with TraceAnnotation("step"):
            self.params, self.opt, self.lstate, m = jax.block_until_ready(
                self.exes[b](self.params, self.opt, self.lstate, batch))
        t2 = time.perf_counter()
        with TraceAnnotation("metrics"):
            m = train_lib.finalize_metrics(m, self.luffy)
        t3 = time.perf_counter()
        with TraceAnnotation("bucket"):
            self.observed_rate = (0.8 * self.observed_rate
                                  + 0.2 * m["condense_rate"])
            if self.luffy.enable_condensation and self.i >= 3:
                self.bucket = train_lib.pick_bucket_host(
                    self.luffy, 0.0, self.observed_rate)
        t4 = time.perf_counter()
        self.i += 1
        return {"bucket": b, "total_s": t4 - t0, "upload_s": t1 - t0,
                "step_s": t2 - t1, "metrics_s": t3 - t2,
                "bucket_s": t4 - t3, "t0": t0, "t4": t4,
                "loss": m["loss"], "condense_rate": m["condense_rate"],
                "dispatch_drop": m["dispatch_drop"],
                "combine_drop": m["combine_drop"],
                "tokens": int((batch_np["labels"] >= 0).sum())}

    # -- readings the comparison needs ----------------------------------------
    def grad1_norms(self, ref_mod) -> Dict[str, float]:
        """Per-leaf norms of the first clipped gradient, worked out from
        AdamW's first moment after one step: mu = (1 - b1) g."""
        import jax
        b1 = JOB_OPT["b1"]
        f = jax.jit(lambda mu: ref_mod.leaf_norms(
            {n: a / (1.0 - b1)
             for n, a in weights.from_program(self.layout, mu).items()}))
        return {n: float(v) for n, v in f(self.opt.mu).items()}

    def change_norms(self, ref_mod) -> Dict[str, float]:
        """Per-leaf norms of the parameters' change since the seed's
        weights (regenerated from the seed, not kept)."""
        import jax
        conf, layout = self.cell.conf, self.layout

        def f(p, key):
            p0 = weights.canonical(layout, conf, key)
            now = weights.from_program(layout, p)
            return ref_mod.leaf_norms({n: now[n] - p0[n] for n in p0})

        return {n: float(v) for n, v in jax.jit(f)(self.params,
                                                   self.key).items()}


def check_steps(prog: Program, pool, ref_mod,
                bucket: Optional[int] = None) -> dict:
    """Set-up's first steps from the seed's weights, through the
    window's own call and feed on distinct batches: the program's side
    of the comparison. ``bucket`` forces the rate bucket's executable;
    by default the first steps run as the launcher runs them."""
    out = {"losses": [], "drops": [], "rates": []}
    for t in range(CHECK_STEPS):
        r = prog.step(pool[t], bucket=bucket)
        out["losses"].append(r["loss"])
        out["rates"].append(r["condense_rate"])
        out["drops"].append(max(r["dispatch_drop"], r["combine_drop"] or 0.0))
        if t == 0:
            out["grad1"] = prog.grad1_norms(ref_mod)
    out["change"] = prog.change_norms(ref_mod)
    return out


# ---------------------------------------------------------------------------
# the reference, and the numbers compared
# ---------------------------------------------------------------------------

def reference_side(cell: Cell, seed: int, pool, *, rate=0.0, lower=False,
                   fault=None) -> dict:
    """The plain reference's three steps from the seed's weights, with
    expert capacity for condensation rate ``rate`` (or the control,
    ``lower=True``, or a planted fault), on one device."""
    import jax
    ref = reference_module(cell.conf)
    layout = layout_module(cell.conf)
    conf = cell.conf
    key = weights.seed_key(seed)
    p0 = jax.jit(lambda k: weights.canonical(layout, conf, k))(key)
    S = cell.traffic["seq_len"]
    res = ref.train3(conf, p0, pool[:CHECK_STEPS], JOB_OPT, lower=lower,
                     group=min(128, S), rate=rate, fault=fault)

    def f(p, k):
        p0 = weights.canonical(layout, conf, k)
        return ref.leaf_norms({n: p[n] - p0[n] for n in p0})

    change = {n: float(v) for n, v in jax.jit(f)(res.pop("params"),
                                                 key).items()}
    return {"losses": res["losses"], "grad1": res["grad1"],
            "change": change}


def _leaf_gaps(got: Dict[str, float], ref: Dict[str, float], leaves
               ) -> List[float]:
    med = statistics.median(ref[n] for n in leaves)
    return [abs(got[n] - ref[n]) / max(ref[n], med) for n in leaves]


def leaf_gaps(got: dict, ref: dict) -> Dict[str, Dict[str, float]]:
    """Per leaf, the gap of the first gradient's norm (every leaf) and of
    the change's norm (leaves that move): each over the reference leaf's
    norm or the median leaf's, whichever is larger. Leaves whose
    reference gradient is under a thousandth of the median leaf's move
    by round-off alone and are left out of the change."""
    leaves = sorted(ref["grad1"])
    gmed = statistics.median(ref["grad1"].values())
    moving = [n for n in leaves if ref["grad1"][n] >= 1e-3 * gmed]
    return {"grad1": dict(zip(leaves, _leaf_gaps(got["grad1"], ref["grad1"],
                                                 leaves))),
            "change": dict(zip(moving, _leaf_gaps(got["change"],
                                                  ref["change"], moving)))}


def numbers(got: dict, ref: dict) -> Dict[str, float]:
    """The numbers compared: each step's loss (relative gap); the first
    gradient's norm by the worst leaf (``grad1``) and by the median leaf
    (``grad1_median``); the change's norm by the worst leaf. A leaf's gap
    is the gap of the two norms over the reference leaf's norm or the
    median leaf's, whichever is larger (``leaf_gaps``)."""
    out = {}
    for t in range(CHECK_STEPS):
        lr = ref["losses"][t]
        out[f"loss_step{t + 1}"] = abs(got["losses"][t] - lr) / abs(lr)
    gaps = leaf_gaps(got, ref)
    out["grad1"] = max(gaps["grad1"].values())
    out["grad1_median"] = statistics.median(gaps["grad1"].values())
    out["change"] = max(gaps["change"].values())
    for k, v in out.items():
        if not math.isfinite(v):
            out[k] = NOT_FINITE      # JSON has no infinity or NaN
    return out


def judge(nums: Dict[int, Dict[str, float]], limits: Optional[dict]
          ) -> Dict[str, dict]:
    """Each compared number of each rate bucket checked (``b<bucket>.
    <number>``) beside its limit. The limits file names the numbers
    compared for the cell, one limit for every bucket; with no file yet,
    every number is shown with no limit (and the run is not correct)."""
    names = [k for k in NUMBERS if limits is None or k in limits]
    return {f"b{b}.{k}": {"value": n[k],
                          "limit": None if limits is None else limits[k]}
            for b, n in sorted(nums.items()) for k in names}


def passed(checks: Dict[str, dict]) -> bool:
    return bool(checks) and all(
        c["limit"] is not None and c["value"] <= c["limit"]
        for c in checks.values())


# ---------------------------------------------------------------------------
# per-layer readers
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RunRecord:
    """What the per-layer readers read: the window's steps, its length,
    the device and, in a traced run, the reduced trace."""
    steps: List[dict]
    window_s: float
    chips: int
    device_kind: str
    flops_per_token: float
    trace: Optional[dict] = None


def read_per_layer(cell: Cell, rec: RunRecord) -> Dict[str, dict]:
    out = {}
    for m in cell.per_layer:
        mod = load_module(ROOT / "metrics" / f"{m['name']}.py")
        v = mod.read(rec)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# faults planted underneath the timed path (for the tests of ``correct``)
# ---------------------------------------------------------------------------

def plant(prog: Program, fault: Optional[str], buckets=None) -> None:
    """Break the timed path in the executables of ``buckets`` (all by
    default): ``stale_state`` makes the step return the state it was
    given; ``half_batch`` leaves out half of the batch, the mean taken
    over the rest; ``token`` alters one token of the batch."""
    if fault is None:
        return
    if fault not in ("stale_state", "half_batch", "token"):
        raise ValueError(f"unknown fault {fault!r}")
    for b, exe in list(prog.exes.items()):
        if buckets is None or b in buckets:
            prog.exes[b] = _broken(exe, fault, prog.cfg.vocab_size)


def _broken(exe, fault: str, vocab: int):
    import jax
    import jax.numpy as jnp

    def step(p, o, lstate, batch):
        if fault == "stale_state":
            keep = jax.tree.map(jnp.copy, (p, o))
            out = exe(p, o, lstate, batch)
            return keep[0], keep[1], out[2], out[3]
        batch = dict(batch)
        if fault == "half_batch":
            lab = batch["labels"]
            batch["labels"] = lab.at[lab.shape[0] // 2:].set(-1)
        else:
            tok = batch["tokens"]
            batch["tokens"] = tok.at[0, 0].set((tok[0, 0] + 1) % vocab)
        return exe(p, o, lstate, batch)

    return step


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts JAX traces and backend compiles while ``on``."""

    def __init__(self):
        import jax
        self.on = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, _secs, **_kw):
        if self.on and name in ("/jax/core/compile/backend_compile_duration",
                                "/jax/core/compile/jaxpr_trace_duration"):
            self.n += 1


def run_cell(prog: Program, seed: int, seconds: float, trace: bool,
             t_start: float, counter: CompileCounter,
             trace_dir: Optional[Path] = None) -> dict:
    """Set-up from ``seed`` on a built program, the measured window, the
    reference and the comparison. Returns the result line's fields, with
    ``checks`` last, and ``_info`` for the earlier lines."""
    import jax
    import numpy as np
    import flops
    import scopes
    import tracereduce

    cell = prog.cell
    pool = traffic_mod.make_pool(cell.traffic, prog.cfg.vocab_size, seed)
    prog.init_state(seed)
    if 0 not in prog.exes:
        prog.compile(pool[0], [0])
    ref_mod = reference_module(cell.conf)
    got = {0: check_steps(prog, pool, ref_mod)}
    # The window may drive every bucket that its condensation rate
    # reaches: none but bucket 0 where no token condenses (the program
    # leaves condensation off where the group does not divide the
    # sequence), else all. Each is compiled, then checked by three steps
    # of its own from the seed's weights; the window goes on from the
    # last of these states, at the launcher's fourth step.
    if any(r > 0 for r in got[0]["rates"]):
        others = range(1, len(prog.luffy.rate_buckets))
        prog.compile(pool[0], [b for b in others if b not in prog.exes])
        for b in others:
            prog.free_state()
            prog.init_state(seed)
            got[b] = check_steps(prog, pool, ref_mod, bucket=b)
    k = CHECK_STEPS
    setup_s = time.perf_counter() - t_start

    window = min(seconds, TRACE_CAP_S) if trace else seconds
    steps = []
    counter.on = True
    prof = None
    if trace:
        prof = jax.profiler.trace(str(trace_dir))
        prof.__enter__()
    try:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < window:
            steps.append(prog.step(pool[k % len(pool)]))
            k += 1
        window_s = steps[-1]["t4"] - t0
    finally:
        if prof is not None:
            prof.__exit__(None, None, None)
        counter.on = False
    stats = [d.memory_stats() or {} for d in prog.devices]
    # On the TPU the buffers in use are about the step's arguments alone;
    # the runtime reserves a region of its own beside them, which the
    # step's temporaries need, so both count.
    peak = max(s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
               for s in stats)
    dev = prog.devices[0]
    fpt = flops.train_flops_per_token(
        prog.layout, prog.struct, cell.conf,
        traffic_mod.lengths(cell.traffic))

    reduced = None
    if trace:
        recs = tracereduce.load(trace_dir)
        win = tracereduce.window_of(recs)
        reduced = tracereduce.reduce(recs, win)
        reduced["scopes"] = scopes.of_run(
            recs, win, prog.exes, steps,
            getattr(prog.layout, "SCOPE_CLASSES", ()) + scopes.CLASSES)
    drops = [d for g in got.values() for d in g["drops"]] + [
        max(s["dispatch_drop"], s["combine_drop"] or 0.0) for s in steps]
    memory = {"allocator": stats[0], "executables": {
        b: exe_memory(exe) for b, exe in sorted(prog.exes.items())}}
    prog.free_state()

    refs = {b: reference_side(cell, seed, pool,
                              rate=prog.luffy.rate_buckets[b])
            for b in got}
    nums = {b: numbers(got[b], refs[b]) for b in got}
    checks = judge(nums, cell.limits)
    failed = sum(1 for s in steps if not math.isfinite(s["loss"]))
    out = {"correct": passed(checks) and failed == 0,
           "attempted": len(steps), "failed": failed,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(prog.devices),
                      "memory_peak_bytes": int(peak)}}
    if trace:
        busy = list(reduced["busy_s"].values())
        out["device"]["busy_s"] = sum(busy) / len(busy)
        out["device"]["window_s"] = reduced["window_s"]
        rec = RunRecord(steps, window_s, cell.chips, dev.device_kind, fpt,
                        reduced)
        out["metrics"] = read_per_layer(cell, rec)
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    else:
        tokens = sum(s["tokens"] for s in steps)
        e2e = {"tokens_per_s": tokens / window_s,
               "step_ms_p90": 1e3 * float(np.percentile(
                   [s["total_s"] for s in steps], 90)),
               "setup_s": setup_s}
        out["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
    out["checks"] = checks
    out["_info"] = {"setup_s": setup_s, "compiles_in_window": counter.n,
                    "steps": len(steps), "window_s": window_s,
                    "max_drop": max(drops), "numbers": nums,
                    "program_losses": {b: g["losses"] for b, g in got.items()},
                    "reference_losses": {b: r["losses"]
                                         for b, r in refs.items()},
                    "buckets": sorted({s["bucket"] for s in steps}),
                    "timeline": timeline(steps), "memory": memory,
                    "flops_per_token": fpt}
    if trace:
        out["_info"]["scopes"] = reduced["scopes"]
    return out


def timeline(steps: List[dict], parts: int = 4) -> List[dict]:
    """The window in ``parts`` runs of steps: steps, mean step time and
    time blocked on the step, steps per rate bucket and mean
    condensation rate of each."""
    out = []
    n = len(steps)
    for q in range(parts):
        part = steps[q * n // parts:(q + 1) * n // parts]
        if not part:
            continue
        out.append({
            "steps": len(part),
            "ms": 1e3 * statistics.fmean(s["total_s"] for s in part),
            "device_ms": 1e3 * statistics.fmean(s["step_s"] for s in part),
            "buckets": {b: sum(s["bucket"] == b for s in part)
                        for b in sorted({s["bucket"] for s in part})},
            "condense_rate": statistics.fmean(s["condense_rate"]
                                              for s in part)})
    return out


def exe_memory(exe) -> Optional[dict]:
    """The compiler's memory analysis of one executable, in bytes."""
    ma = exe.memory_analysis() if hasattr(exe, "memory_analysis") else None
    if ma is None:
        return None
    return {k: int(getattr(ma, f"{k}_size_in_bytes"))
            for k in ("argument", "output", "alias", "temp")}
