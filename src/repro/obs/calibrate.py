"""Measured cost-model calibration (DESIGN.md §11, ROADMAP item 5).

Every planning decision in this repo prices against analytic models
with hand-set constants: link bandwidths/latencies
(:class:`repro.comm.Topology`), the per-chunk pipeline overhead
(``repro.sched.cost.DEFAULT_CHUNK_OVERHEAD_MS``), the planning-cost
slope (``repro.plan.estimate.PLAN_STEP_US``), the similarity and FFN
compute speeds (``estimate_similarity_ms``, ``LuffyConfig.gpu_speed``).
This module *measures* each of those on the running backend:

* **collectives** — flat/hier all-to-all and psum timed at several
  payload sizes; a linear fit ``t = lat + bytes / bw`` per link tier
  recovers effective bandwidth and message latency;
* **per-chunk overhead** — ``k`` dependency-chained collectives on the
  same payload vs one, the residual beyond the fitted message latency;
* **pipeline stages** — the expert-FFN einsum chain and the
  condensation Gram matmul, timed and converted to effective FLOP/s
  under the same flop conventions the estimators use (so the fitted
  speeds are drop-in replacements for ``gpu_speed`` / ``speed``);
* **planning** — the host migration greedy
  (``plan_migration_with_objective``) timed over several slot counts,
  slope converted to a per-slot ``step_us``.

The fit persists as a **versioned artifact** keyed exactly like
:class:`repro.plan.cache.PlanCache` entries — topology fingerprint +
backend (:func:`calibration_key`) — so a stale fingerprint, foreign
backend, or schema bump is a *miss* (remeasure), never a misread.
:meth:`Calibration.topology` / :meth:`Calibration.apply` /
:meth:`Calibration.estimate_kwargs` feed the fit into
``Topology``/``LuffyConfig``/``estimate_exchange`` so the ``overlap``
objective, planned chunk counts and the dryrun ledger run on measured
numbers. ``benchmarks/fig_calibration.py`` asserts held-out
predicted-vs-measured agreement.
"""
from __future__ import annotations

import dataclasses
import json
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.comm.topology import Topology

CALIBRATION_MAGIC = "repro-calibration"
CALIBRATION_SCHEMA_VERSION = 1

# Clamp rails for degenerate fits (two near-equal timing points on a
# noisy host can produce a negative slope): bandwidths in bytes/s,
# latencies in seconds, speeds in FLOP/s.
_MIN_BW, _MAX_BW = 1e6, 1e13
_MIN_LAT, _MAX_LAT = 0.0, 1.0
_MIN_SPEED, _MAX_SPEED = 1e6, 1e16


def calibration_key(topo: Optional[Topology], M: int,
                    backend: Optional[str] = None) -> str:
    """Artifact key: the PlanCache topology fingerprint extended with the
    jax backend the numbers were measured on (a CPU fit must never price
    a TPU run)."""
    from repro.plan.cache import topology_fingerprint
    if backend is None:
        import jax
        backend = jax.default_backend()
    return f"{topology_fingerprint(topo, M)}__{backend}"


@dataclasses.dataclass(frozen=True)
class Calibration:
    """One measured fit, bound to (topology fingerprint, backend).

    Bandwidths bytes/s, latencies seconds, speeds FLOP/s under the
    estimator conventions (``4·d·d_ff`` per FFN row, ``4·d`` per
    measured similarity pair). ``samples`` keeps the raw (bytes,
    seconds) measurements for audit/plotting; it is persisted but never
    read back into pricing.
    """
    key: str
    intra_bw: float
    inter_bw: float
    intra_lat: float
    inter_lat: float
    chunk_overhead_ms: float
    plan_step_us: float
    sim_speed: float
    ffn_speed: float
    samples: Dict[str, Any] = dataclasses.field(default_factory=dict)
    schema_version: int = CALIBRATION_SCHEMA_VERSION

    # -- pricing hand-off ----------------------------------------------------
    def topology(self, base: Topology) -> Topology:
        """``base`` with measured link speeds/latencies — what the
        launchers hand to ``make_dist`` so the migration link-cost
        matrix, ledger and overlap model all price measured links."""
        return base.with_links(
            intra_bw=self.intra_bw, inter_bw=self.inter_bw,
            intra_lat=self.intra_lat, inter_lat=self.inter_lat)

    def apply(self, luffy):
        """``luffy`` with the measured compute speed and chunk overhead
        (``LuffyConfig.chunk_overhead_ms``; ≤0 means the built-in
        default, see ``repro.sched.cost``)."""
        return dataclasses.replace(
            luffy, gpu_speed=self.ffn_speed,
            chunk_overhead_ms=self.chunk_overhead_ms)

    def estimate_kwargs(self) -> Dict[str, float]:
        """Overrides for :func:`repro.plan.estimate.estimate_exchange`."""
        return {"intra_bw": self.intra_bw, "inter_bw": self.inter_bw,
                "chunk_overhead_ms": self.chunk_overhead_ms}

    # -- serialization -------------------------------------------------------
    def to_json(self) -> str:
        payload = {"magic": CALIBRATION_MAGIC, **dataclasses.asdict(self)}
        return json.dumps(payload, indent=1, sort_keys=True)

    @classmethod
    def from_json(cls, text: str,
                  expect_key: Optional[str] = None
                  ) -> Optional["Calibration"]:
        """Parse an artifact; None (a miss) on any mismatch: wrong
        magic, schema drift, or — when ``expect_key`` is given — a stale
        topology fingerprint / backend."""
        try:
            payload = json.loads(text)
        except (ValueError, TypeError):
            return None
        if not isinstance(payload, dict):
            return None
        if payload.pop("magic", None) != CALIBRATION_MAGIC:
            return None
        if payload.get("schema_version") != CALIBRATION_SCHEMA_VERSION:
            return None
        if expect_key is not None and payload.get("key") != expect_key:
            return None
        fields = {f.name for f in dataclasses.fields(cls)}
        if not fields.issubset(payload):
            return None
        try:
            return cls(**{k: payload[k] for k in fields})
        except (TypeError, ValueError):
            return None


def _artifact_path(out_dir, key: str) -> Path:
    return Path(out_dir) / f"{key}.calib.json"


def save_calibration(out_dir, calib: Calibration) -> Path:
    path = _artifact_path(out_dir, calib.key)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(calib.to_json())
    return path


def load_calibration(out_dir, key: str) -> Optional[Calibration]:
    """Artifact for ``key``, or None (miss: absent, corrupt, version
    drift, or written for another fingerprint/backend)."""
    path = _artifact_path(out_dir, key)
    if not path.exists():
        return None
    try:
        text = path.read_text()
    except OSError:
        return None
    return Calibration.from_json(text, expect_key=key)


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------

def _timeit(fn, *args, repeats: int = 3) -> float:
    """Best-of-``repeats`` wall seconds of ``fn(*args)``, blocking on the
    result (one untimed warmup absorbs compilation)."""
    import jax
    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best


def _fit_bw_lat(samples: Sequence[Tuple[float, float]]
                ) -> Tuple[float, float]:
    """Least-squares ``t = lat + bytes/bw`` over (bytes, seconds)
    samples, clamped to physical rails."""
    xs = np.array([s[0] for s in samples], np.float64)
    ys = np.array([s[1] for s in samples], np.float64)
    if len(xs) < 2 or float(np.ptp(xs)) == 0.0:
        bw = float(xs.mean() / max(ys.mean(), 1e-12)) if len(xs) else _MIN_BW
        return float(np.clip(bw, _MIN_BW, _MAX_BW)), 0.0
    slope, intercept = np.polyfit(xs, ys, 1)
    bw = 1.0 / max(float(slope), 1e-14)
    lat = max(float(intercept), 0.0)
    return (float(np.clip(bw, _MIN_BW, _MAX_BW)),
            float(np.clip(lat, _MIN_LAT, _MAX_LAT)))


def _a2a_fn(mesh, axis: str, chain: int = 1):
    """jitted shard_map'd chain of ``chain`` dependent tiled all_to_alls
    over ``axis``."""
    import jax
    from jax.sharding import PartitionSpec as P

    def f(x):
        for _ in range(chain):
            x = jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                                   tiled=True)
        return x
    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(axis),
                                 out_specs=P(axis)))


def _psum_fn(mesh, axis: str):
    import jax
    from jax.sharding import PartitionSpec as P

    def f(x):
        return jax.lax.psum(x, axis)
    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(axis),
                                 out_specs=P()))


def _payload(mesh, axis: str, rows: int, d: int):
    """[size(axis)·rows, d] f32 sharded over ``axis`` on dim 0 (so each
    device holds ``rows`` rows split into size(axis) exchange chunks)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    size = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    x = jnp.ones((size * rows, d), jnp.float32)
    return jax.device_put(x, NamedSharding(mesh, P(axis)))


def measure_all_to_all(mesh, axis: str, rows_list: Sequence[int],
                       d: int = 256) -> List[Tuple[float, float]]:
    """(off-device bytes per device, seconds) of one tiled all_to_all
    over ``axis`` at each payload size."""
    size = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    fn = _a2a_fn(mesh, axis)
    out = []
    for rows in rows_list:
        x = _payload(mesh, axis, rows, d)
        t = _timeit(fn, x)
        off_bytes = (size - 1) / size * rows * d * 4.0
        out.append((off_bytes, t))
    return out


def measure_psum(mesh, axis: str, rows_list: Sequence[int],
                 d: int = 256) -> List[Tuple[float, float]]:
    """(payload bytes per device, seconds) of one psum over ``axis``."""
    import jax
    import jax.numpy as jnp
    fn = _psum_fn(mesh, axis)
    out = []
    for rows in rows_list:
        x = jnp.ones((rows, d), jnp.float32)
        t = _timeit(fn, x)
        out.append((rows * d * 4.0, t))
    return out


def measure_chunk_overhead_ms(mesh, axis: str, topo: Topology, *,
                              rows: int = 512, d: int = 256,
                              chain: int = 4,
                              intra_lat: float = 0.0,
                              inter_lat: float = 0.0) -> float:
    """Per-chunk issue cost beyond message latency: ``chain`` dependent
    all_to_alls vs one, residual per extra collective minus the fitted
    per-message latencies (the quantity ``sched.cost.overlap_ms`` adds
    on top of ``chunk_latency_s``)."""
    from repro.comm.ledger import phase_messages
    x = _payload(mesh, axis, rows, d)
    t1 = _timeit(_a2a_fn(mesh, axis, 1), x)
    tk = _timeit(_a2a_fn(mesh, axis, chain), x)
    per_extra_s = max(0.0, (tk - t1) / max(1, chain - 1) - t1)
    mi, me = phase_messages(topo)
    lat_s = mi * intra_lat + me * inter_lat
    return float(np.clip((per_extra_s - lat_s) * 1e3, 1e-4, 1e3))


def measure_plan_step_us(M: int, *, q: int = 3,
                         slot_counts: Sequence[int] = (16, 32, 64)
                         ) -> Tuple[float, List[Tuple[float, float]]]:
    """Fitted per-slot cost (µs) of one migration replan, from timing
    the host greedy at several slot counts (the best available proxy for
    ``estimate_planning_ms``'s scan-latency slope on this backend)."""
    from repro.plan.estimate import PLAN_DEVICE_US
    from repro.plan.objectives import plan_migration_with_objective
    rng = np.random.default_rng(0)
    samples = []
    for n_slots in slot_counts:
        counts = np.floor(rng.random((n_slots, M)) ** 3 * 16.0)
        lens = rng.permutation(np.arange(8, 8 + n_slots)).astype(np.float64)
        n_per_dev = max(1, n_slots // M)

        def run():
            return plan_migration_with_objective(counts, lens, n_per_dev,
                                                 q=q)
        run()                                    # warmup
        t0 = time.perf_counter()
        run()
        samples.append((float(n_slots), time.perf_counter() - t0))
    xs = np.array([s[0] for s in samples])
    ys = np.array([s[1] for s in samples])
    slope_us = float(np.polyfit(xs, ys, 1)[0]) * 1e6 if len(xs) > 1 \
        else float(ys[0] / xs[0]) * 1e6
    step_us = max(slope_us - PLAN_DEVICE_US * M * max(1, q), 0.01)
    return step_us, samples


def measure_sim_speed(*, group: int = 64, d: int = 256
                      ) -> Tuple[float, float]:
    """(effective FLOP/s, seconds) of one condensation Gram build, under
    the ``pairs · 4 · d`` convention of ``estimate_similarity_ms``."""
    import jax
    import jax.numpy as jnp
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (group, d)), jnp.float32)
    fn = jax.jit(lambda a: a @ a.T)
    t = _timeit(fn, x)
    pairs = group * (group - 1) / 2.0
    speed = pairs * 4.0 * d / max(t, 1e-9)
    return float(np.clip(speed, _MIN_SPEED, _MAX_SPEED)), t


def measure_ffn_speed(*, rows: int = 512, d: int = 256, d_ff: int = 1024
                      ) -> Tuple[float, float]:
    """(effective FLOP/s, seconds) of the gated expert-FFN einsum chain,
    under the ``rows · 4 · d · d_ff`` convention the exchange planner
    prices ``ffn_ms`` with (a fitted *effective* speed: the real chain
    has three matmuls, the convention two — calibration absorbs that)."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((rows, d)), jnp.float32)
    wu = jnp.asarray(rng.standard_normal((d, d_ff)) / np.sqrt(d),
                     jnp.float32)
    wg = jnp.asarray(rng.standard_normal((d, d_ff)) / np.sqrt(d),
                     jnp.float32)
    wd = jnp.asarray(rng.standard_normal((d_ff, d)) / np.sqrt(d_ff),
                     jnp.float32)

    def f(x):
        h = jax.nn.silu(x @ wg) * (x @ wu)
        return h @ wd
    t = _timeit(jax.jit(f), x)
    speed = rows * 4.0 * d * d_ff / max(t, 1e-9)
    return float(np.clip(speed, _MIN_SPEED, _MAX_SPEED)), t


# ---------------------------------------------------------------------------
# the full run
# ---------------------------------------------------------------------------

def run_calibration(mesh, topo: Optional[Topology], *,
                    out_dir=None, quick: bool = True,
                    force: bool = False) -> Calibration:
    """Measure everything on ``mesh``'s backend and return the fit
    (loading a previously-persisted artifact for the same key from
    ``out_dir`` instead of re-measuring, and persisting fresh fits
    there — the PlanCache load-before-build discipline).

    ``mesh=None`` (or a mesh with no expert axis) skips the collective
    fits and keeps the topology's built-in link constants; compute and
    planning fits always run. ``force=True`` skips the cached-artifact
    load and overwrites it with a fresh fit — the drift detector's
    recalibration path (``--recalibrate-on-drift``): a fit that no
    longer matches reality must not satisfy its own cache key.
    """
    from repro.comm.topology import model_axes_of
    M = topo.num_devices if topo is not None else 1
    axes = model_axes_of(tuple(mesh.axis_names)) if mesh is not None \
        else None
    key = calibration_key(topo, M)
    if out_dir is not None and not force:
        cached = load_calibration(out_dir, key)
        if cached is not None:
            return cached

    rows_list = (64, 256, 1024) if quick else (64, 256, 1024, 4096)
    samples: Dict[str, Any] = {"rows_list": list(rows_list)}
    intra_bw = topo.intra_bw if topo is not None else _MAX_BW
    inter_bw = topo.inter_bw if topo is not None else _MAX_BW
    intra_lat = topo.intra_lat if topo is not None else 0.0
    inter_lat = topo.inter_lat if topo is not None else 0.0
    chunk_overhead_ms = -1.0

    if mesh is not None and axes is not None and topo is not None:
        if isinstance(axes, tuple):               # ("node", "local")
            node_ax, local_ax = axes
            intra_samples = measure_all_to_all(mesh, local_ax, rows_list)
            inter_samples = measure_all_to_all(mesh, node_ax, rows_list)
            intra_bw, intra_lat = _fit_bw_lat(intra_samples)
            inter_bw, inter_lat = _fit_bw_lat(inter_samples)
            samples["a2a_intra"] = intra_samples
            samples["a2a_inter"] = inter_samples
            samples["psum"] = measure_psum(mesh, local_ax, rows_list[:2])
            overhead_ax = local_ax
        else:                                     # flat "model"
            flat_samples = measure_all_to_all(mesh, axes, rows_list)
            intra_bw, intra_lat = _fit_bw_lat(flat_samples)
            inter_bw, inter_lat = intra_bw, intra_lat
            samples["a2a_intra"] = flat_samples
            samples["psum"] = measure_psum(mesh, axes, rows_list[:2])
            overhead_ax = axes
        chunk_overhead_ms = measure_chunk_overhead_ms(
            mesh, overhead_ax, topo, intra_lat=intra_lat,
            inter_lat=inter_lat)
    if chunk_overhead_ms <= 0.0:
        from repro.sched.cost import DEFAULT_CHUNK_OVERHEAD_MS
        chunk_overhead_ms = DEFAULT_CHUNK_OVERHEAD_MS

    plan_step_us, plan_samples = measure_plan_step_us(max(M, 2))
    samples["planning"] = plan_samples
    sim_speed, sim_t = measure_sim_speed()
    samples["similarity_s"] = sim_t
    ffn_speed, ffn_t = measure_ffn_speed()
    samples["ffn_s"] = ffn_t

    calib = Calibration(
        key=key, intra_bw=intra_bw, inter_bw=inter_bw,
        intra_lat=intra_lat, inter_lat=inter_lat,
        chunk_overhead_ms=chunk_overhead_ms, plan_step_us=plan_step_us,
        sim_speed=sim_speed, ffn_speed=ffn_speed,
        # canonicalize (tuples -> lists) so the in-memory fit equals its
        # serialized round trip
        samples=json.loads(json.dumps(samples)))
    if out_dir is not None:
        save_calibration(out_dir, calib)
    return calib


# ---------------------------------------------------------------------------
# trace-mode phase probe
# ---------------------------------------------------------------------------

def probe_exchange(cfg, luffy, *, n_seq: int = 2,
                   seq_len: Optional[int] = None, seed: int = 0):
    """Drive ONE representative gate → plan-build → execute exchange
    *eagerly* on this device, so an active tracer records real fenced
    plan_build / condense / dispatch / expert_ffn / combine phase spans.

    The jitted train step hides those phases structurally: the
    transformer forward scans over layer groups and ``lax.scan`` traces
    its body even outside ``jit``, so the library ``phase()`` hooks can
    never fire through ``forward_train``. The probe is the ``--trace``
    mode's source of per-phase timings — same code path
    (``build_exchange_plan``/``execute_plan``), representative shapes,
    single-device collectives. Returns (y, aux).
    """
    import jax
    import jax.numpy as jnp
    from repro.comm import CommContext
    from repro.core import moe_layer
    S = seq_len if seq_len is not None else 64
    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    params = moe_layer.moe_init(k1, cfg)
    x = jax.random.normal(k2, (n_seq, S, cfg.d_model), jnp.float32)
    sideband = {"labels": jnp.zeros((n_seq, S), jnp.int32),
                "seq_len": jnp.full((n_seq,), S, jnp.float32)}
    capacity = moe_layer.capacity_for(cfg.moe, n_seq * S,
                                      cfg.moe.num_experts)
    y, _sb, _sn, aux = moe_layer.moe_core(
        params, x, sideband, cfg, luffy, mode="vanilla",
        capacity=capacity, threshold=jnp.float32(0.95),
        group_size=min(luffy.condense_group, S),
        combine_slack=luffy.combine_slack, comm=CommContext.local())
    jax.block_until_ready(y)
    return y, aux


def probe_exchange_per_device(cfg, luffy, *, n_seq: int = 1,
                              seq_len: Optional[int] = None,
                              seed: int = 0,
                              max_devices: int = 8) -> Dict[int, float]:
    """Run :func:`probe_exchange` once pinned to each local device and
    return ``{device_index: wall_ms}`` — the straggler probe.

    Each repetition runs under a ``probe_exchange`` span tagged
    ``device=i``, which ``Tracer.to_chrome`` maps onto its own Perfetto
    row; the returned dict feeds
    :func:`repro.obs.monitor.device_dispersion`. On a single-device
    backend this degenerates to one entry (dispersion 1.0) — cheap and
    harmless."""
    import time

    import jax

    from repro.obs import trace as obs_trace
    out: Dict[int, float] = {}
    for i, dev in enumerate(jax.local_devices()[:max_devices]):
        with jax.default_device(dev):
            with obs_trace.phase("probe_exchange", cat="probe",
                                 device=i):
                t0 = time.perf_counter()
                probe_exchange(cfg, luffy, n_seq=n_seq, seq_len=seq_len,
                               seed=seed)
                out[i] = (time.perf_counter() - t0) * 1e3
    return out
