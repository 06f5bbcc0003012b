"""Train-step builders: loss + grad + AdamW update, LUFFY state threading.

The adaptive condensation threshold (paper Eq. 2) is a *runtime scalar*
computed in-step from the running loss; the condensation *rate bucket*
(which fixes the static dispatch capacity) is chosen host-side between
steps — one compiled executable per bucket, cached (DESIGN.md §3).
"""
from __future__ import annotations

import dataclasses
from collections.abc import Mapping
from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.config import LuffyConfig, ModelConfig, OptimConfig, ShapeConfig
from repro.core import moe_layer
from repro.core.condensation import adaptive_threshold
from repro.dist import DistContext
from repro.models import transformer as tf
from repro.obs import trace as obs_trace


class LuffyState(NamedTuple):
    l_ini: jnp.ndarray     # loss at iteration 1 (Eq. 2)
    l_prev: jnp.ndarray    # loss at t-1
    step: jnp.ndarray
    # Cross-step wire error-feedback buffer (DESIGN.md §15): previous
    # step's per-layer payload quantization residuals, shape
    # tf.wire_ef_shape(cfg, B, S). None unless
    # LuffyConfig.wire_error_feedback is on under a lossy wire_dtype.
    wire_ef: Optional[jnp.ndarray] = None


def init_luffy_state(wire_ef_shape: Optional[Tuple[int, ...]] = None
                     ) -> LuffyState:
    ef = (jnp.zeros(wire_ef_shape, jnp.float32)
          if wire_ef_shape is not None else None)
    return LuffyState(jnp.float32(-1.0), jnp.float32(-1.0), jnp.int32(0),
                      ef)


# device-to-host pulls made by finalize_metrics: one per StepMetrics, one
# per device value of a plain dict
PULLS = 0


@jax.tree_util.register_pytree_node_class
class StepMetrics(Mapping):
    """A train step's scalar metrics, packed on the device into one f32
    vector so that the host pulls them in one transfer (DESIGN.md §11).
    The names, in the step's order, are static pytree aux data. As a
    ``Mapping`` it reads like the step's old dict: ``m["loss"]`` is the
    device slice."""

    def __init__(self, names: Tuple[str, ...], values):
        self.names = names
        self.values = values
        self._index = {k: i for i, k in enumerate(names)}

    @classmethod
    def pack(cls, metrics: Dict[str, Any]) -> "StepMetrics":
        values = []
        for k, v in metrics.items():
            v = jnp.asarray(v)
            # only an f32 scalar round-trips bit for bit as its f32 slot
            if v.shape != () or v.dtype != jnp.float32:
                raise ValueError(f"metric {k!r} is not an f32 scalar: "
                                 f"{v.dtype}{list(v.shape)}")
            values.append(v)
        return cls(tuple(metrics), jnp.stack(values))

    def tree_flatten(self):
        return (self.values,), self.names

    @classmethod
    def tree_unflatten(cls, names, leaves):
        return cls(names, *leaves)

    def __getitem__(self, key):
        return self.values[self._index[key]]

    def __iter__(self):
        return iter(self.names)

    def __len__(self):
        return len(self.names)


def tokens_per_device(cfg: ModelConfig, shape: ShapeConfig,
                      dist: DistContext) -> int:
    div = dist.batch_size_divisor
    if dist.seq_axis is not None:
        div *= dist.axis_size(dist.seq_axis)
    return max(1, shape.global_batch * shape.seq_len // max(1, div))


def capacity_for_bucket(cfg: ModelConfig, shape: ShapeConfig,
                        dist: DistContext, luffy: LuffyConfig,
                        bucket: int) -> int:
    rate = luffy.rate_buckets[bucket] if luffy.enable_condensation else 0.0
    return moe_layer.capacity_for(
        cfg.moe, tokens_per_device(cfg, shape, dist),
        cfg.moe.num_experts, rate=rate)


def loss_and_metrics(params, batch, lstate: LuffyState, cfg, luffy, dist,
                     capacity):
    if luffy.adaptive_threshold:
        have = lstate.l_ini > 0
        thr = jnp.where(have, adaptive_threshold(lstate.l_ini,
                                                 lstate.l_prev),
                        jnp.float32(0.999))
    else:
        thr = jnp.float32(luffy.static_threshold)
    return tf.forward_train(params, cfg, luffy, dist, batch, thr, capacity,
                            wire_ef=lstate.wire_ef)


def make_train_step(cfg: ModelConfig, luffy: LuffyConfig,
                    ocfg: OptimConfig, dist: DistContext, capacity: int,
                    param_pspecs=None):
    """Returns step(params, opt_state, lstate, batch) ->
    (params, opt_state, lstate, metrics), metrics a ``StepMetrics``.
    Not yet jitted (callers attach shardings / donation).

    param_pspecs: if given, gradients are sharding-constrained back to the
    parameter layout right after value_and_grad — without this, grads of
    shard_map inputs (spec P('model',…)) stay data-axis-replicated and the
    transient f32 grad tree blows past HBM (ZeRO grad resharding)."""

    def step(params, opt_state, lstate, batch):
        def lf(p):
            loss, metrics = loss_and_metrics(p, batch, lstate, cfg, luffy,
                                             dist, capacity)
            return loss, metrics

        (loss, metrics), grads = jax.value_and_grad(lf, has_aux=True)(params)
        if param_pspecs is not None and dist.enabled:
            grads = jax.tree.map(
                lambda g, sp: dist.constrain(g, sp), grads, param_pspecs)
        with obs_trace.phase("optimizer"):
            params, opt_state, ometrics = optim.update(params, grads,
                                                       opt_state, ocfg)
        metrics = dict(metrics)
        ef_next = metrics.pop("_wire_ef", None)
        metrics.update(ometrics)
        metrics["total_loss"] = loss
        new_l = metrics["loss"]
        lstate2 = LuffyState(
            jnp.where(lstate.l_ini > 0, lstate.l_ini, new_l),
            new_l, lstate.step + 1,
            ef_next if ef_next is not None else lstate.wire_ef)
        return params, opt_state, lstate2, StepMetrics.pack(metrics)

    return step


def make_eval_step(cfg: ModelConfig, luffy: LuffyConfig, dist: DistContext,
                   capacity: int):
    no_luffy = dataclasses.replace(luffy, enable_condensation=False,
                                   enable_migration=False)

    def step(params, batch):
        loss, metrics = tf.forward_train(params, cfg, no_luffy, dist, batch,
                                         jnp.float32(1.0), capacity)
        return metrics

    return step


def finalize_metrics(metrics, luffy: LuffyConfig):
    """Host-side view of one step's metrics: device scalars pulled to
    python floats (a ``StepMetrics`` in one transfer, a plain dict value
    by value), config-inapplicable keys masked to ``None`` (an
    ``inter_bytes_shipped`` of 0.0 from a dense-wire run means "nothing
    measured", not "zero bytes"; see ``repro.obs.metrics``)."""
    from repro.obs import metrics as obs_metrics
    global PULLS
    if isinstance(metrics, StepMetrics):
        PULLS += 1
        out = dict(zip(metrics.names, np.asarray(metrics.values).tolist()))
    else:
        out = {}
        for k, v in metrics.items():
            PULLS += isinstance(v, jax.Array)
            try:
                out[k] = float(v)
            except (TypeError, ValueError):
                out[k] = v
    return obs_metrics.mask_inapplicable(out, luffy)


def pick_bucket_host(luffy: LuffyConfig, threshold: float,
                     observed_rate: float) -> int:
    """Host-side bucket selection: the largest capacity-reduction bucket
    that the *observed* condensation rate supports (hysteresis of one
    bucket to avoid recompile thrash)."""
    best = 0
    for i, r in enumerate(luffy.rate_buckets):
        if r <= max(0.0, observed_rate - 0.05):
            best = i
    return best
