"""Optimizers in pure JAX: AdamW (with ZeRO-1-friendly moment sharding),
SGD+momentum, global-norm clipping, warmup-cosine schedule.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import OptimConfig


class OptState(NamedTuple):
    step: jnp.ndarray
    mu: Any          # first moments  (pytree like params, f32)
    nu: Any          # second moments (pytree like params, f32)


def _factored(p) -> bool:
    return p.ndim >= 2 and p.shape[-1] >= 128 and p.shape[-2] >= 128


def init_opt_state(params, cfg: OptimConfig) -> OptState:
    """AdamW: f32 mu/nu. Adafactor: bf16 mu + factored f32 nu (row/col
    second-moment estimates) — the memory-viable choice for 100B+ MoE
    (full f32 Adam moments for llama4-400b are 24 GB/device at maximal
    sharding on a 256-chip pod; factored states are ~params/4096)."""
    # zeros_like keeps each parameter's sharding: the moments of
    # expert-sharded weights are sharded the same way
    if cfg.name == "adafactor":
        mu = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.bfloat16), params)

        def nu_init(p):
            if _factored(p):
                return {"r": jnp.zeros(p.shape[:-1], jnp.float32),
                        "c": jnp.zeros(p.shape[:-2] + p.shape[-1:],
                                       jnp.float32)}
            return jnp.zeros(p.shape, jnp.float32)

        return OptState(jnp.zeros((), jnp.int32), mu,
                        jax.tree.map(nu_init, params))
    zeros = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)
    return OptState(jnp.zeros((), jnp.int32), zeros,
                    jax.tree.map(jnp.copy, zeros))


def lr_schedule(cfg: OptimConfig, step):
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    prog = jnp.clip((step - cfg.warmup_steps)
                    / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
                    0.0, 1.0)
    cos = 0.5 * (1.0 + jnp.cos(jnp.pi * prog))
    return cfg.lr * warm * (0.1 + 0.9 * cos)


def clip_by_global_norm(grads, max_norm: float):
    leaves = jax.tree.leaves(grads)
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                      for g in leaves))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-9))
    return jax.tree.map(lambda g: g * scale.astype(g.dtype), grads), gn


def _decay_mask(path) -> bool:
    """No weight decay for norms / biases / 1-d params."""
    keys = [getattr(k, "key", getattr(k, "name", "")) for k in path]
    flat = "/".join(str(k) for k in keys)
    return not any(s in flat for s in ("norm", "scale", "bias", "mix_",
                                       "dt_bias", "a_log", "d_skip",
                                       "w_bias", "u_bonus"))


def adamw_update(params, grads, state: OptState, cfg: OptimConfig
                 ) -> Tuple[Any, OptState, Dict[str, jnp.ndarray]]:
    grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip)
    step = state.step + 1
    lr = lr_schedule(cfg, step)
    b1, b2 = cfg.b1, cfg.b2
    bc1 = 1.0 - b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - b2 ** step.astype(jnp.float32)

    def upd(path, p, g, m, v):
        gf = g.astype(jnp.float32)
        m2 = b1 * m + (1 - b1) * gf
        v2 = b2 * v + (1 - b2) * gf * gf
        mh = m2 / bc1
        vh = v2 / bc2
        delta = mh / (jnp.sqrt(vh) + cfg.eps)
        if _decay_mask(path):
            delta = delta + cfg.weight_decay * p.astype(jnp.float32)
        p2 = p.astype(jnp.float32) - lr * delta
        return p2.astype(p.dtype), m2, v2

    out = jax.tree_util.tree_map_with_path(
        lambda path, p, g, m, v: upd(path, p, g, m, v),
        params, grads, state.mu, state.nu)
    # unzip the 3-tuples
    new_params = jax.tree.map(lambda t: t[0], out,
                              is_leaf=lambda t: isinstance(t, tuple))
    new_mu = jax.tree.map(lambda t: t[1], out,
                          is_leaf=lambda t: isinstance(t, tuple))
    new_nu = jax.tree.map(lambda t: t[2], out,
                          is_leaf=lambda t: isinstance(t, tuple))
    return new_params, OptState(step, new_mu, new_nu), \
        {"grad_norm": gnorm, "lr": lr}


def adafactor_update(params, grads, state: OptState, cfg: OptimConfig):
    """Adafactor with momentum (bf16 mu, factored f32 nu) + weight decay."""
    grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip)
    step = state.step + 1
    lr = lr_schedule(cfg, step)
    b2 = cfg.b2

    def upd(path, p, g, m, v):
        gf = g.astype(jnp.float32)
        g2 = gf * gf + 1e-30
        if isinstance(v, dict):
            r = b2 * v["r"] + (1 - b2) * jnp.mean(g2, axis=-1)
            c = b2 * v["c"] + (1 - b2) * jnp.mean(g2, axis=-2)
            rc = r[..., None] * c[..., None, :]
            denom = rc / jnp.maximum(
                jnp.mean(r, axis=-1)[..., None, None], 1e-30)
            v2 = {"r": r, "c": c}
        else:
            denom = b2 * v + (1 - b2) * g2
            v2 = denom
        u = gf / (jnp.sqrt(denom) + cfg.eps)
        m2 = cfg.b1 * m.astype(jnp.float32) + (1 - cfg.b1) * u
        delta = m2
        if _decay_mask(path):
            delta = delta + cfg.weight_decay * p.astype(jnp.float32)
        p2 = p.astype(jnp.float32) - lr * delta
        return p2.astype(p.dtype), m2.astype(m.dtype), v2

    # NB: trees 2..4 are flattened up-to params' structure, so a factored
    # nu arrives at `upd` as its whole {"r","c"} dict.
    out = jax.tree_util.tree_map_with_path(
        upd, params, grads, state.mu, state.nu)

    def is3(t):
        return isinstance(t, tuple) and len(t) == 3
    new_params = jax.tree.map(lambda t: t[0], out, is_leaf=is3)
    new_mu = jax.tree.map(lambda t: t[1], out, is_leaf=is3)
    new_nu = jax.tree.map(lambda t: t[2], out, is_leaf=is3)
    return new_params, OptState(step, new_mu, new_nu), \
        {"grad_norm": gnorm, "lr": lr}


def sgd_update(params, grads, state: OptState, cfg: OptimConfig):
    grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip)
    step = state.step + 1
    lr = lr_schedule(cfg, step)
    mu = jax.tree.map(lambda m, g: 0.9 * m + g.astype(jnp.float32),
                      state.mu, grads)
    params = jax.tree.map(
        lambda p, m: (p.astype(jnp.float32) - lr * m).astype(p.dtype),
        params, mu)
    return params, OptState(step, mu, state.nu), \
        {"grad_norm": gnorm, "lr": lr}


def update(params, grads, state, cfg: OptimConfig):
    if cfg.name == "sgd":
        return sgd_update(params, grads, state, cfg)
    if cfg.name == "adafactor":
        return adafactor_update(params, grads, state, cfg)
    return adamw_update(params, grads, state, cfg)
