"""Beyond-paper deliverable (DESIGN.md §11): measured-vs-predicted
calibration of the cost model on the running backend.

``repro.obs.calibrate`` times real collectives (tiled all_to_all per
link tier, psum), the dependency-chained pipeline issue overhead, the
host migration planner, the similarity Gram build and the expert FFN,
and fits the cost-model constants the planner/estimator otherwise takes
on faith. This benchmark runs the fit, then CHECKS it:

* held-out prediction — an all_to_all payload size the fit never saw
  must be predicted by ``lat + bytes/bw`` within ``TOL``× (generous: CPU
  collectives jitter, but a fit that is off by an order of magnitude
  would silently mis-rank migration plans);
* compute fits are stable across shape — re-measuring the FFN/similarity
  speed at a different shape stays within ``TOL``× of the fitted speed;
* the artifact round-trips through its versioned serializer, a stale
  topology fingerprint / bumped schema loads as a MISS, and the
  load-before-measure path returns the persisted fit verbatim;
* the ``phase()`` trace hook costs <5% on an untraced step (the
  ``--trace`` overhead budget: an untraced host call pays a trace-state
  check and one module-global comparison per hook).

Emits CSV rows and ``artifacts/fig_calibration.json``; the artifact
itself lands in ``artifacts/calib/<key>.calib.json``.
"""
from __future__ import annotations

import json
import time

from benchmarks.common import ARTIFACTS, emit

TOL = 4.0          # held-out prediction tolerance (ratio, either way)
HOOK_BUDGET = 0.05  # phase() overhead budget on an untraced step


def _ratio(pred: float, meas: float) -> float:
    lo = max(min(pred, meas), 1e-12)
    return max(pred, meas) / lo


def _held_out_link(calib, mesh, axis: str, bw: float, lat: float):
    """Predict one all_to_all the fit never saw (rows=512) on ``axis``."""
    from repro.obs.calibrate import measure_all_to_all
    ((off_bytes, t_meas),) = measure_all_to_all(mesh, axis, [512])
    t_pred = lat + off_bytes / bw
    return off_bytes, t_meas, t_pred


def _hook_overhead_ratio() -> float:
    """Relative cost of the phase() hook with NO tracer active, around
    a real jitted step (best-of medians; min damps scheduler noise)."""
    import jax
    import jax.numpy as jnp
    from repro.obs import trace as obs_trace
    obs_trace.deactivate()
    x = jnp.ones((256, 256), jnp.float32)
    step = jax.jit(lambda a: a @ a.T + 1.0)
    jax.block_until_ready(step(x))

    def loop_plain():
        y = x
        for _ in range(20):
            y = step(y)
        jax.block_until_ready(y)

    def loop_hooked():
        y = x
        for _ in range(20):
            with obs_trace.phase("step") as sp:
                y = sp.fence(step(y))
        jax.block_until_ready(y)

    def best(fn, reps: int = 7) -> float:
        fn()
        out = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            out = min(out, time.perf_counter() - t0)
        return out

    return best(loop_hooked) / best(loop_plain)


def run(fast: bool = True) -> None:
    import jax
    from repro.launch.mesh import make_host_mesh, topology_for_mesh
    from repro.obs.calibrate import (Calibration, load_calibration,
                                     run_calibration)

    mesh = topo = None
    if len(jax.devices()) >= 4:
        nodes = 2
        model = min(4, len(jax.devices()))
        mesh = make_host_mesh(model=model, nodes=nodes)
        topo = topology_for_mesh(mesh)

    out_dir = ARTIFACTS / "calib"
    t0 = time.time()
    calib = run_calibration(mesh, topo, out_dir=out_dir, quick=fast)
    fit_s = time.time() - t0
    rows = [("calibration/fit", fit_s * 1e6, calib.key)]
    result = {"key": calib.key, "fit_s": fit_s, "tolerance": TOL,
              "intra_bw": calib.intra_bw, "inter_bw": calib.inter_bw,
              "chunk_overhead_ms": calib.chunk_overhead_ms,
              "plan_step_us": calib.plan_step_us,
              "sim_speed": calib.sim_speed,
              "ffn_speed": calib.ffn_speed, "held_out": {}}

    # -- held-out predicted vs measured (collectives: hier mesh only) ------
    if mesh is not None:
        for axis, bw, lat in (("local", calib.intra_bw, calib.intra_lat),
                              ("node", calib.inter_bw, calib.inter_lat)):
            off_bytes, t_meas, t_pred = _held_out_link(
                calib, mesh, axis, bw, lat)
            r = _ratio(t_pred, t_meas)
            rows.append((f"calibration/held_out_{axis}", t_meas * 1e6,
                         f"pred={t_pred*1e6:.1f}us ratio={r:.2f}"))
            result["held_out"][axis] = {
                "bytes": off_bytes, "measured_s": t_meas,
                "predicted_s": t_pred, "ratio": r}
            assert r <= TOL, (
                f"{axis} all_to_all held-out prediction off {r:.1f}x "
                f"(> {TOL}x): measured {t_meas:.2e}s vs predicted "
                f"{t_pred:.2e}s for {off_bytes:.0f}B")

    # -- compute fits stable across shape ----------------------------------
    from repro.obs.calibrate import measure_ffn_speed, measure_sim_speed
    ffn2, _ = measure_ffn_speed(rows=256, d=256, d_ff=1024)
    sim2, _ = measure_sim_speed(group=128, d=256)
    for name, fitted, again in (("ffn_speed", calib.ffn_speed, ffn2),
                                ("sim_speed", calib.sim_speed, sim2)):
        r = _ratio(fitted, again)
        rows.append((f"calibration/{name}_stability", 0.0,
                     f"fit={fitted:.3g} heldout={again:.3g} "
                     f"ratio={r:.2f}"))
        result["held_out"][name] = {"fitted": fitted,
                                    "remeasured": again, "ratio": r}
        assert r <= TOL, \
            f"{name} unstable across shapes: {fitted:.3g} vs {again:.3g}"

    # -- artifact contract -------------------------------------------------
    back = Calibration.from_json(calib.to_json(), expect_key=calib.key)
    assert back == calib, "calibration artifact does not round-trip"
    stale_key = calib.key.replace("__", "STALE__", 1)
    assert Calibration.from_json(calib.to_json(),
                                 expect_key=stale_key) is None, \
        "stale topology fingerprint must load as a miss"
    cached = load_calibration(out_dir, calib.key)
    assert cached == calib, "persisted artifact must load verbatim"
    assert run_calibration(mesh, topo, out_dir=out_dir) == calib, \
        "load-before-measure must return the persisted fit"
    rows.append(("calibration/artifact_roundtrip", 0.0, "ok"))

    # -- trace-hook overhead budget ----------------------------------------
    overhead = min(_hook_overhead_ratio() for _ in range(3)) - 1.0
    rows.append(("calibration/phase_hook_overhead", 0.0,
                 f"{overhead*100:.2f}%"))
    result["phase_hook_overhead"] = overhead
    assert overhead < HOOK_BUDGET, (
        f"untraced phase() hook overhead {overhead*100:.1f}% exceeds "
        f"the {HOOK_BUDGET*100:.0f}% budget")

    emit(rows)
    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    (ARTIFACTS / "fig_calibration.json").write_text(
        json.dumps(result, indent=1))


if __name__ == "__main__":
    run()
