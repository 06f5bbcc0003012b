"""Model stacks: decoder-only / encoder-decoder, dense / MoE / SSM / hybrid.

Layers are grouped by the architecture's *pattern period* (lcm of the
window pattern and the FFN pattern) and scanned with ``lax.scan`` over
groups — one group's HLO regardless of depth, which keeps 48-layer
dry-run compiles cheap. Params for pattern position ``j`` are stacked
``[n_groups, ...]``.

Train/prefill use full-sequence attention; decode uses per-layer KV ring
buffers (window layers) or full caches (global layers), written as plain
sharded-array code so GSPMD inserts the context-parallel collectives.
The MoE sublayer is the exception: it runs in an explicit shard_map
(see ``repro.core.moe_layer``).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import comm as rcomm
from repro.config import LuffyConfig, ModelConfig
from repro.core import moe_layer as moe
from repro.dist import DistContext
from repro.models import blocks as bk
from repro.models import ssm as ssm_mod
from repro.obs import trace as obs_trace

Array = jnp.ndarray


def pattern_period(cfg: ModelConfig) -> int:
    a = len(cfg.attn.window_pattern) if cfg.attn is not None else 1
    b = len(cfg.layer_ffn_pattern)
    return math.lcm(a, b)


def _uses_ssm(cfg: ModelConfig) -> bool:
    return cfg.ssm is not None


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_layer(key, cfg: ModelConfig, j: int, *, decoder_of_encdec: bool):
    ks = jax.random.split(key, 8)
    pdt = bk._dtype(cfg.param_dtype)
    p: Dict[str, Any] = {}
    if cfg.attn is not None:
        p["attn_norm"] = bk.norm_init(cfg.d_model, cfg.norm, pdt)
        p["attn"] = bk.attn_init(ks[0], cfg)
    if cfg.ssm is not None:
        if cfg.ssm.kind == "mamba":
            p["ssm"] = ssm_mod.mamba_init(ks[1], cfg)
        else:
            p["ssm"] = ssm_mod.rwkv6_init(ks[1], cfg)
        if cfg.attn is None or not cfg.parallel_ssm:
            p["ssm_norm"] = bk.norm_init(cfg.d_model, cfg.norm, pdt)
    if decoder_of_encdec:
        p["cross_norm"] = bk.norm_init(cfg.d_model, cfg.norm, pdt)
        p["cross_attn"] = bk.attn_init(ks[2], cfg, cross=True)
    kind = cfg.ffn_kind(j)
    if kind == "moe":
        p["moe"] = moe.moe_init(ks[3], cfg)
    else:
        p["ffn_norm"] = bk.norm_init(cfg.d_model, cfg.norm, pdt)
        if cfg.ssm is not None and cfg.ssm.kind == "rwkv6":
            p["ffn"] = ssm_mod.rwkv_cmix_init(ks[4], cfg)
        else:
            p["ffn"] = bk.ffn_init(ks[4], cfg.d_model, cfg.d_ff, cfg)
    return p


def init_params(key, cfg: ModelConfig):
    period = pattern_period(cfg)
    assert cfg.num_layers % period == 0, (cfg.name, cfg.num_layers, period)
    n_groups = cfg.num_layers // period
    pdt = bk._dtype(cfg.param_dtype)
    keys = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "embed": {"table": bk.embed_init(keys[0], cfg.vocab_size,
                                         cfg.d_model, pdt)},
        "final_norm": bk.norm_init(cfg.d_model, cfg.norm, pdt),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = {"w": bk.dense_init(keys[1], cfg.d_model,
                                                cfg.vocab_size, pdt)}
    if cfg.prefix_slots > 0:
        params["prefix_proj"] = {"w": bk.dense_init(
            keys[2], cfg.prefix_dim or cfg.d_model, cfg.d_model, pdt)}

    def stack_layers(base_key, n, j, decoder_of_encdec):
        lkeys = jax.random.split(base_key, n)
        return jax.vmap(lambda k: _init_layer(
            k, cfg, j, decoder_of_encdec=decoder_of_encdec))(lkeys)

    params["layers"] = [stack_layers(jax.random.fold_in(keys[3], j),
                                     n_groups, j,
                                     decoder_of_encdec=(cfg.kind == "encdec"))
                        for j in range(period)]
    if cfg.kind == "encdec":
        enc_groups = cfg.num_encoder_layers // period
        assert enc_groups * period == cfg.num_encoder_layers
        params["encoder"] = {
            "layers": [stack_layers(jax.random.fold_in(keys[4], 100 + j),
                                    enc_groups, j, decoder_of_encdec=False)
                       for j in range(period)],
            "final_norm": bk.norm_init(cfg.d_model, cfg.norm, pdt),
        }
    return params


# ---------------------------------------------------------------------------
# full-sequence layer (train / prefill / encoder)
# ---------------------------------------------------------------------------

def _attn_seqpar(p, cfg, xn, positions, layer_idx, *, causal, dist,
                 kv_valid=None, kv_src=None, kv_src_pos=None):
    """Sequence-parallel attention: S is sharded over dist.seq_axis, so
    each device attends its LOCAL query chunk against all-gathered K/V
    (one bf16 gather per layer). Without this, the chunked-attention
    lax.map serializes the q-chunk axis and GSPMD replicates the whole
    attention on every model rank (observed: prefill memory terms blowing
    up by the axis size)."""
    a = cfg.attn
    mesh = dist.mesh
    sax = dist.seq_axis
    bax = dist.batch_axes if dist.batch_axes else None
    import math as _math
    cdt = bk._dtype(cfg.compute_dtype)

    has_kvv = kv_valid is not None
    has_src = kv_src is not None

    def inner(p_l, x_l, pos_l, kvv_l, src_l, spos_l):
        kvv_l = kvv_l if has_kvv else None
        src_l = src_l if has_src else None
        spos_l = spos_l if has_src else None
        xq = x_l.astype(cdt)
        q = bk._split_heads(xq @ p_l["wq"].astype(cdt), a.num_heads,
                            a.head_dim)
        src = xq if src_l is None else src_l.astype(cdt)
        k = bk._split_heads(src @ p_l["wk"].astype(cdt), a.num_kv_heads,
                            a.head_dim)
        v = bk._split_heads(src @ p_l["wv"].astype(cdt), a.num_kv_heads,
                            a.head_dim)
        kpos_l = pos_l if src_l is None else spos_l
        if a.use_rope:
            q = bk.apply_rope(q, pos_l, a.rope_theta)
            if src_l is None:
                k = bk.apply_rope(k, kpos_l, a.rope_theta)
        # gather keys/values (+positions/validity) across the seq shards.
        # The optimization barrier pins the gathered buffers: without it
        # XLA sinks the (loop-invariant) gather INTO the q-chunk loop and
        # re-gathers K/V per chunk — observed 512 gathers instead of 48
        # on gemma3 prefill (EXPERIMENTS.md §Perf H1).
        k_g = jax.lax.all_gather(k, sax, axis=1, tiled=True)
        v_g = jax.lax.all_gather(v, sax, axis=1, tiled=True)
        kp_g = jax.lax.all_gather(kpos_l, sax, axis=1, tiled=True)
        k_g, v_g, kp_g = jax.lax.optimization_barrier((k_g, v_g, kp_g))
        kv_g = (None if kvv_l is None
                else jax.lax.all_gather(kvv_l, sax, axis=1, tiled=True))
        scale = a.softmax_scale or 1.0 / _math.sqrt(a.head_dim)
        window = a.window_for_layer(layer_idx) if src_l is None else None
        is_causal = causal and src_l is None
        if max(q.shape[1], k_g.shape[1]) > bk.ATTN_DIRECT_MAX:
            out = bk.attend_chunked(
                q, k_g, v_g, pos_l[0], kp_g[0], scale, causal=is_causal,
                window=window, chunked_window=a.chunked_local,
                logit_cap=a.logit_cap, kv_valid=kv_g)
        else:
            mask = bk.make_attn_mask(pos_l, kp_g, causal=is_causal,
                                     window=window,
                                     chunked=a.chunked_local)
            if kv_g is not None:
                mask = mask & kv_g[:, None, :]
            out = bk.attend(q, k_g, v_g, mask, scale, a.logit_cap)
        out = out.reshape(out.shape[:-2] + (a.q_dim,))
        return (out @ p_l["wo"].astype(cdt)).astype(x_l.dtype)

    x_spec = P(bax, sax, None)
    pos_spec = P(bax, sax)
    p_specs = jax.tree.map(lambda _: P(), p)
    kvv = kv_valid
    fn = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(p_specs, x_spec, pos_spec,
                  pos_spec if kvv is not None else P(),
                  x_spec if kv_src is not None else P(),
                  pos_spec if kv_src is not None else P()),
        out_specs=x_spec)
    return fn(p, xn, positions,
              kvv if kvv is not None else jnp.zeros((), jnp.int32),
              kv_src if kv_src is not None else jnp.zeros((), jnp.int32),
              kv_src_pos if kv_src_pos is not None
              else jnp.zeros((), jnp.int32))


def _token_mixer_full(p, cfg, x, positions, layer_idx, *, causal, enc_out,
                      enc_pos, dist: DistContext, kv_valid=None):
    """Attention and/or SSM sublayer (+cross-attn), full sequence."""
    out_kv = None
    seqpar = (dist.enabled and dist.seq_axis is not None
              and cfg.attn is not None)

    def self_attn(xn):
        with obs_trace.phase("attention"):
            if seqpar:
                return _attn_seqpar(p["attn"], cfg, xn, positions,
                                    layer_idx, causal=causal, dist=dist,
                                    kv_valid=kv_valid), None
            return bk.attn_apply(p["attn"], cfg, xn, positions,
                                 layer=layer_idx, causal=causal,
                                 kv_valid=kv_valid)

    if cfg.attn is not None and cfg.ssm is not None and cfg.parallel_ssm:
        xn = bk.norm_apply(p["attn_norm"], x, cfg.norm)
        att, out_kv = self_attn(xn)
        sso = ssm_mod.mamba_apply(p["ssm"], cfg, xn)
        x = x + 0.5 * (att + sso)
    elif cfg.attn is not None:
        xn = bk.norm_apply(p["attn_norm"], x, cfg.norm)
        att, out_kv = self_attn(xn)
        x = x + att
    else:  # pure SSM (rwkv6)
        xn = bk.norm_apply(p["ssm_norm"], x, cfg.norm)
        if cfg.ssm.kind == "mamba":
            x = x + ssm_mod.mamba_apply(p["ssm"], cfg, xn)
        else:
            x = x + ssm_mod.rwkv6_apply(p["ssm"], cfg, xn)
    if enc_out is not None:
        xn = bk.norm_apply(p["cross_norm"], x, cfg.norm)
        if seqpar:
            ca = _attn_seqpar(p["cross_attn"], cfg, xn, positions,
                              layer_idx, causal=False, dist=dist,
                              kv_src=enc_out, kv_src_pos=enc_pos)
        else:
            ca, _ = bk.attn_apply(p["cross_attn"], cfg, xn, positions,
                                  layer=layer_idx, kv=(enc_out, enc_pos),
                                  causal=False)
        x = x + ca
    return x, out_kv


def _moe_apply_dist(p_moe, x, sideband, s_prev, threshold, cfg, luffy,
                    dist: DistContext, mode: str, capacity: int,
                    plan_carry=None, cond_carry=None, plan_template=None,
                    wire_ef=None):
    """Wrap moe_core in shard_map when a mesh is present.

    plan_carry (DESIGN.md §9): the cross-sublayer plan-reuse state —
    ``{"counts", "lens", "valid"}`` global arrays threaded through the
    layer scan; None disables threading (the return slot is then None).
    cond_carry (DESIGN.md §10): the condense-reuse state — ``{"rep"
    [B,S], "cexp" [B,S], "age" [B], "valid" [B]}`` — threaded the same
    way whenever condensation is on (every ``condense_reuse`` mode, for
    graph parity).
    plan_template: a cached static :class:`ExchangePlan` template (the
    serving path) routed to ``instantiate_plan`` instead of a build.
    wire_ef (DESIGN.md §15): the per-layer lossy-wire error-feedback
    residual [B, S, d] (sharded like x); None disables threading.
    Returns (y, sideband, s_next, aux, plan_carry_out, cond_carry_out,
    wire_ef_out)."""
    from repro.condense.plan import CondenseCarry
    from repro.plan.exchange import PlanSignature
    if mode == "decode" and dist.enabled and dist.model_size > 1:
        # decode: tokens replicated over the model axis; all-reduce MoE
        # (see moe_decode_allreduce — the S=1 token dim cannot shard)
        mesh = dist.mesh
        all_axes = tuple(mesh.axis_names)
        bax = dist.batch_axes if dist.batch_axes else None
        # 2D expert sharding for decode (REPRO_MOE_DECODE_2D=0 restores
        # the weight-gather baseline — the §Perf "before" variant): the
        # FSDP'd expert weights stay sharded; activations psum instead.
        import os as _os
        fsdp = tuple(a for a in dist.fsdp_axes if a in all_axes)
        n_fsdp = dist.axis_size(fsdp) if fsdp else 1
        use_2d = (_os.environ.get("REPRO_MOE_DECODE_2D", "1") == "1"
                  and fsdp
                  and cfg.moe.d_ff % n_fsdp == 0)
        ma = dist.model_axis          # "model" or ("node", "local")
        moe_specs = jax.tree.map(lambda _: P(), p_moe)
        if use_2d:
            moe_specs["experts"] = {
                k: (P(ma, fsdp, None) if k == "w_down"
                    else P(ma, None, fsdp))
                for k in p_moe["experts"]}
        else:
            moe_specs["experts"] = jax.tree.map(
                lambda _: P(ma, None, None), p_moe["experts"])

        batch_sharded = bool(dist.batch_axes)

        def inner_dec(p_moe_l, x_l):
            y, aux = moe.moe_decode_allreduce(
                p_moe_l, x_l, cfg, capacity=capacity,
                axis_name=dist.model_axis, use_kernel=luffy.use_kernels,
                fsdp_axes=fsdp if use_2d else None,
                batch_sharded=batch_sharded,
                overlap=luffy.exec_mode == "decode_overlap")
            aux = jax.tree.map(lambda a: rcomm.pmean_all(a, all_axes), aux)
            return y, aux

        fn = jax.shard_map(
            inner_dec, mesh=mesh,
            in_specs=(moe_specs, P(bax, None, None)),
            out_specs=(P(bax, None, None),
                       jax.tree.map(lambda _: P(),
                                    moe.MoEAux(*([0.0] * moe.N_AUX)))))
        y, aux = fn(p_moe, x)
        return y, dict(sideband), None, aux, plan_carry, cond_carry, \
            wire_ef
    if not dist.enabled or dist.model_size == 1:
        sb = dict(sideband)
        reuse = None
        if plan_carry is not None:
            reuse = PlanSignature(plan_carry["counts"], plan_carry["lens"],
                                  plan_carry["valid"])
        creuse = None
        if cond_carry is not None:
            creuse = CondenseCarry(cond_carry["rep"].reshape(-1),
                                   cond_carry["cexp"].reshape(-1),
                                   cond_carry["age"], cond_carry["valid"])
        y, sb2, s_next, aux, plan, cc, ef2 = moe.moe_core_planned(
            p_moe, x, sb, cfg, luffy, mode=mode, capacity=capacity,
            axis_name=None, threshold=threshold, s_prev=s_prev,
            group_size=luffy.condense_group,
            combine_slack=luffy.combine_slack, use_kernel=luffy.use_kernels,
            reuse_from=reuse, condense_reuse_from=creuse,
            plan_template=plan_template, wire_ef=wire_ef)
        if s_next is not None:
            G = luffy.condense_group
            s_next = s_next.reshape(x.shape[0], x.shape[1] // G, G, G)
        carry_out = None
        if plan_carry is not None:
            sig = plan.signature
            carry_out = {"counts": sig.counts, "lens": sig.lens,
                         "valid": sig.valid}
        cond_out = None
        if cond_carry is not None:
            cond_out = cond_carry if cc is None else cc
        return y, sb2, s_next, aux, carry_out, cond_out, \
            (wire_ef if ef2 is None else ef2)

    mesh = dist.mesh
    all_axes = tuple(mesh.axis_names)
    bax = dist.batch_axes if dist.batch_axes else None
    sax = dist.seq_axis
    x_spec = P(bax, sax, None)
    lbl_spec = P(bax, sax)
    len_spec = P(bax)
    sp_spec = P(bax, None, None, None)
    has_sp = s_prev is not None

    fsdp = tuple(a for a in dist.fsdp_axes if a in all_axes)
    comm_ctx = rcomm.CommContext.build(luffy.comm_mode, dist.model_axis,
                                       dist.topology)
    has_pc = plan_carry is not None
    has_cc = cond_carry is not None
    has_ef = wire_ef is not None

    def inner(p_moe_l, x_l, lbl, slen, sp, thr, pcc, pcl, pcv,
              ccr, cce, cca, ccv, efp):
        if fsdp:
            # explicit bf16 FSDP all-gather of the expert F-dim shards;
            # leaving this to GSPMD hoists an f32 convert before the
            # gather on backends that emulate bf16 dots (2x bytes).
            p_moe_l = dict(p_moe_l)
            p_moe_l["experts"] = {
                k: jax.lax.all_gather(
                    w, fsdp, axis=(1 if k == "w_down" else 2), tiled=True)
                for k, w in p_moe_l["experts"].items()}
        sb = {"labels": lbl, "seq_len": slen}
        reuse = PlanSignature(pcc, pcl, pcv) if has_pc else None
        creuse = (CondenseCarry(ccr.reshape(-1), cce.reshape(-1), cca, ccv)
                  if has_cc else None)
        y, sb2, s_next, aux, plan, cc, ef2 = moe.moe_core_planned(
            p_moe_l, x_l, sb, cfg, luffy, mode=mode, capacity=capacity,
            comm=comm_ctx, threshold=thr,
            s_prev=(sp if has_sp else None),
            group_size=luffy.condense_group,
            combine_slack=luffy.combine_slack, use_kernel=luffy.use_kernels,
            reuse_from=reuse, condense_reuse_from=creuse,
            plan_template=plan_template,
            wire_ef=(efp if has_ef else None))
        if has_ef and ef2 is not None:
            efp = ef2
        aux = jax.tree.map(lambda a: rcomm.pmean_all(a, all_axes), aux)
        if s_next is None:
            s_next = jnp.zeros((1,), jnp.float32)    # placeholder
        else:
            ng = x_l.shape[1] // luffy.condense_group
            s_next = s_next.reshape(x_l.shape[0], ng, luffy.condense_group,
                                    luffy.condense_group)
        if has_pc:
            # carried signature: replicated within a model row by
            # construction (all-gathered planner inputs), but specced
            # per-device varying to stay version-robust — mark it so
            sig = plan.signature
            pcc = rcomm.pvary_all(sig.counts, all_axes)
            pcl = rcomm.pvary_all(sig.lens, all_axes)
            pcv = sig.valid
        if has_cc and cc is not None:
            ccr, cce = cc["rep"], cc["cexp"]
            cca, ccv = cc["age"], cc["valid"]
        return (y, sb2["labels"], sb2["seq_len"], s_next, aux,
                pcc, pcl, pcv, ccr, cce, cca, ccv, efp)

    ma = dist.model_axis              # "model" or ("node", "local")
    moe_specs = jax.tree.map(lambda _: P(), p_moe)
    moe_specs["experts"] = {
        k: (P(ma, fsdp if fsdp else None, None) if k == "w_down"
            else P(ma, None, fsdp if fsdp else None))
        for k in p_moe["experts"]}
    sp_in = sp_spec if has_sp else P()
    sp_arg = s_prev if has_sp else jnp.zeros((1,), jnp.float32)
    s_out_spec = sp_spec if (luffy.enable_condensation and mode != "decode") \
        else P()
    zp = jnp.zeros((1,), jnp.float32)
    zpi = jnp.zeros((1,), jnp.int32)
    pc_counts_spec = P(bax, None) if has_pc else P()
    pc_lens_spec = P(bax) if has_pc else P()
    pc_args = ((plan_carry["counts"], plan_carry["lens"],
                plan_carry["valid"]) if has_pc else (zp, zp, zp))
    cc_map_spec = P(bax, None) if has_cc else P()
    cc_seq_spec = P(bax) if has_cc else P()
    cc_args = ((cond_carry["rep"], cond_carry["cexp"], cond_carry["age"],
                cond_carry["valid"]) if has_cc else (zpi, zpi, zp, zp))
    ef_spec = x_spec if has_ef else P()
    ef_arg = wire_ef if has_ef else jnp.zeros((1, 1, 1), jnp.float32)
    fn = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(moe_specs, x_spec, lbl_spec, len_spec, sp_in, P(),
                  pc_counts_spec, pc_lens_spec, P(),
                  cc_map_spec, cc_map_spec, cc_seq_spec, cc_seq_spec,
                  ef_spec),
        out_specs=(x_spec, lbl_spec, len_spec, s_out_spec,
                   jax.tree.map(lambda _: P(),
                                moe.MoEAux(*([0.0] * moe.N_AUX))),
                   pc_counts_spec, pc_lens_spec, P(),
                   cc_map_spec, cc_map_spec, cc_seq_spec, cc_seq_spec,
                   ef_spec))
    (y, lbl2, slen2, s_next, aux, pcc2, pcl2, pcv2,
     ccr2, cce2, cca2, ccv2, ef2) = fn(
        p_moe, x, sideband["labels"], sideband["seq_len"], sp_arg,
        threshold, *pc_args, *cc_args, ef_arg)
    if not (luffy.enable_condensation and mode != "decode"):
        s_next = None
    carry_out = ({"counts": pcc2, "lens": pcl2, "valid": pcv2}
                 if has_pc else None)
    cond_out = ({"rep": ccr2, "cexp": cce2, "age": cca2, "valid": ccv2}
                if has_cc else None)
    return (y, {"labels": lbl2, "seq_len": slen2}, s_next, aux, carry_out,
            cond_out, (ef2 if has_ef else None))


def _layer_full(p, cfg, luffy, dist, x, sideband, s_prev, threshold,
                j, *, causal, enc_out, enc_pos, moe_mode, capacity,
                plan_carry=None, cond_carry=None, wire_ef=None):
    # NOTE: the window pattern repeats with the scan period, so the static
    # pattern position ``j`` fully determines this layer's window — no
    # traced layer index may reach ``window_for_layer``.
    positions = jnp.broadcast_to(
        jnp.arange(x.shape[1], dtype=jnp.int32)[None], x.shape[:2])
    kv_valid = None
    if not causal:
        # non-causal archs (MoE-BERT): padded keys must not be attended
        kv_valid = positions < sideband["seq_len"][:, None]
    x, _ = _token_mixer_full(p, cfg, x, positions, j, causal=causal,
                             enc_out=enc_out, enc_pos=enc_pos, dist=dist,
                             kv_valid=kv_valid)
    x = dist.constrain(x, dist.act_spec())
    kind = cfg.ffn_kind(j)
    if kind == "moe":
        (x, sideband, s_prev, aux, plan_carry, cond_carry,
         wire_ef) = _moe_apply_dist(
            p["moe"], x, sideband, s_prev, threshold, cfg, luffy, dist,
            moe_mode, capacity, plan_carry=plan_carry,
            cond_carry=cond_carry, wire_ef=wire_ef)
        x = dist.constrain(x, dist.act_spec())
    else:
        xn = bk.norm_apply(p["ffn_norm"], x, cfg.norm)
        if cfg.ssm is not None and cfg.ssm.kind == "rwkv6":
            x = x + ssm_mod.rwkv_cmix_apply(p["ffn"], cfg, xn)
        else:
            x = x + bk.ffn_apply(p["ffn"], cfg, xn)
        aux = moe.MoEAux(*([jnp.float32(0.0)] * moe.N_AUX))
    return x, sideband, s_prev, aux, plan_carry, cond_carry, wire_ef


# ---------------------------------------------------------------------------
# embedding / logits / loss
# ---------------------------------------------------------------------------

def embed_tokens(params, cfg: ModelConfig, tokens, prefix=None,
                 dist: Optional[DistContext] = None):
    """Token embedding. The table is d-sharded over 'model'; when the
    batch is also sharded over 'model' (expert-parallel train shapes) the
    gather can't keep both, so we stage it: batch over the data axes only
    -> local gather (d over model) -> reshard to the activation spec.
    Without staging, GSPMD replicates the batch (observed: 1.25 GiB
    [256,4096,320] buffers dominating the llama4 memory profile)."""
    with obs_trace.phase("embed"):
        return _embed_tokens(params, cfg, tokens, prefix, dist)


def _embed_tokens(params, cfg: ModelConfig, tokens, prefix, dist):
    cdt = bk._dtype(cfg.compute_dtype)
    table = params["embed"]["table"]
    m_axes = () if dist is None else dist.model_axes_tuple
    staged = (dist is not None and dist.enabled
              and any(a in (dist.batch_axes or ()) for a in m_axes))
    if staged:
        from jax.sharding import PartitionSpec as P
        dax = tuple(a for a in dist.batch_axes if a not in m_axes)
        tokens = dist.constrain(tokens, P(dax or None, dist.seq_axis))
    x = jnp.take(table, tokens, axis=0).astype(cdt)
    if staged:
        x = dist.constrain(x, P(dax or None, dist.seq_axis,
                                dist.model_axis))
        x = dist.constrain(x, dist.act_spec())
    x = x * jnp.sqrt(jnp.float32(cfg.d_model)).astype(cdt)
    if prefix is not None:
        px = (prefix.astype(cdt) @ params["prefix_proj"]["w"].astype(cdt))
        x = jnp.concatenate([px, x], axis=1)
    return x


def logits_fn(params, cfg: ModelConfig, x):
    cdt = bk._dtype(cfg.compute_dtype)
    h = bk.norm_apply(params["final_norm"], x, cfg.norm).astype(cdt)
    if cfg.tie_embeddings:
        w = params["embed"]["table"].astype(cdt).T
    else:
        w = params["unembed"]["w"].astype(cdt)
    return h @ w


def chunked_xent(params, cfg, x, labels, *, chunk: int = 512):
    """Cross-entropy over S in chunks to bound logits memory.

    labels < 0 are ignored. Returns (sum_loss, count)."""
    with obs_trace.phase("lm_head"):
        return _chunked_xent(params, cfg, x, labels, chunk)


def _chunked_xent(params, cfg, x, labels, chunk):
    B, S, _ = x.shape
    chunk = min(chunk, S)
    n = S // chunk
    rem = S - n * chunk

    def one(xc, lc):
        lg = logits_fn(params, cfg, xc).astype(jnp.float32)
        valid = lc >= 0
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(
            lg, jnp.maximum(lc, 0)[..., None], axis=-1)[..., 0]
        tok_loss = (lse - gold) * valid.astype(jnp.float32)
        return jnp.sum(tok_loss), jnp.sum(valid.astype(jnp.float32))

    if n > 0:
        xs = x[:, :n * chunk].reshape(B, n, chunk, -1).swapaxes(0, 1)
        ls = labels[:, :n * chunk].reshape(B, n, chunk).swapaxes(0, 1)

        def body(c, inp):
            dl, dc = one(inp[0], inp[1])
            return (c[0] + dl, c[1] + dc), None

        (sl, sc), _ = jax.lax.scan(
            body, (jnp.float32(0), jnp.float32(0)), (xs, ls))
    else:
        sl = sc = jnp.float32(0)
    if rem:
        l2, c2 = one(x[:, n * chunk:], labels[:, n * chunk:])
        sl, sc = sl + l2, sc + c2
    return sl, sc


# ---------------------------------------------------------------------------
# the train forward
# ---------------------------------------------------------------------------

def wire_ef_shape(cfg: ModelConfig, batch: int, seq_len: int):
    """Shape of the cross-step wire error-feedback buffer (DESIGN.md
    §15): one per-token residual slot per layer, grouped the way the
    layer scan consumes it — ``(n_groups, period, B, S, d_model)``."""
    period = pattern_period(cfg)
    return (cfg.num_layers // period, period, batch, seq_len, cfg.d_model)


def forward_train(params, cfg: ModelConfig, luffy: LuffyConfig,
                  dist: DistContext, batch: Dict[str, Array], threshold,
                  capacity: int, wire_ef=None):
    """batch: tokens [B,S_tok], labels [B,S], seq_len [B],
    (prefix [B,P,pd] for vlm/audio). Returns (loss, metrics).

    ``wire_ef`` (optional, :func:`wire_ef_shape`): previous step's
    per-layer wire quantization residuals. When given, each MoE layer
    adds its slot to the shipped payload and the refreshed residuals
    come back under ``metrics["_wire_ef"]`` for the caller to carry
    into the next step (LuffyConfig.wire_error_feedback)."""
    period = pattern_period(cfg)
    prefix = batch.get("prefix")
    x = embed_tokens(params, cfg, batch["tokens"], prefix, dist=dist)
    x = dist.constrain(x, dist.act_spec())
    S = x.shape[1]
    sideband = {"labels": batch["labels"],
                "seq_len": batch["seq_len"].astype(jnp.int32)}

    enc_out = enc_pos = None
    if cfg.kind == "encdec":
        enc_x = (batch["enc_input"].astype(x.dtype)
                 @ params["prefix_proj"]["w"].astype(x.dtype))
        enc_x = dist.constrain(enc_x, dist.act_spec())
        enc_out = _run_encoder(params["encoder"], cfg, luffy, dist, enc_x)
        enc_pos = jnp.broadcast_to(
            jnp.arange(enc_out.shape[1], dtype=jnp.int32)[None],
            enc_out.shape[:2])

    use_cond = (luffy.enable_condensation and cfg.uses_moe
                and dist.seq_axis is None)
    G = luffy.condense_group
    if use_cond and S % G == 0:
        # init at 0.5 = "uncertain": block 1 measures everything (§V-A has
        # no history yet); 0.0 would wrongly mark every pair dissimilar.
        s_prev0 = jnp.full((x.shape[0], S // G, G, G), 0.5, jnp.float32)
    else:
        s_prev0 = None
        use_cond = False
    moe_mode = ("migrate" if (luffy.enable_migration and cfg.uses_moe
                              and dist.seq_axis is None) else "vanilla")
    eff_luffy = luffy if use_cond else \
        dataclasses.replace(luffy, enable_condensation=False)

    # Plan-lifecycle carry (DESIGN.md §9): the migration plan's routing
    # signature threads through the layer scan so stable-routing stacks
    # plan once and execute N times. The carry (and the revalidation
    # cond) is threaded for EVERY plan_reuse mode including "off" —
    # "off" pins the carried valid flag to 0 so it always replans — so
    # the compiled graphs of "off" and "signature" are structurally
    # identical and their forwards bit-comparable (the greedy planner
    # has float near-ties; two different compilations may legitimately
    # pick different equally-good plans). Global layout: per-batch-
    # device slot rows stacked data-major — [M·B, M] counts, [M·B] lens
    # (tiny; specced per-device varying for jax-version robustness).
    use_reuse = moe_mode == "migrate" and cfg.uses_moe
    B = x.shape[0]
    if use_reuse:
        M_model = dist.model_size if dist.enabled else 1
        pc0 = {"counts": jnp.zeros((M_model * B, M_model), jnp.float32),
               "lens": jnp.zeros((M_model * B,), jnp.float32),
               "valid": jnp.float32(0.0)}
    else:
        pc0 = {"counts": jnp.zeros((1,), jnp.float32),
               "lens": jnp.zeros((1,), jnp.float32),
               "valid": jnp.float32(0.0)}
    # Condense-reuse carry (DESIGN.md §10): the carried rep map +
    # signature threads through the scan whenever condensation is on —
    # for EVERY condense_reuse mode ("off" pins the valid flag to 0), so
    # the compiled graphs stay structurally identical across modes (the
    # same graph-parity discipline as the migration carry above).
    use_creuse = use_cond
    if use_creuse:
        cc0 = {"rep": jnp.zeros((B, S), jnp.int32),
               "cexp": jnp.zeros((B, S), jnp.int32),
               "age": jnp.zeros((B,), jnp.float32),
               "valid": jnp.zeros((B,), jnp.float32)}
    else:
        cc0 = {"rep": jnp.zeros((1,), jnp.int32),
               "cexp": jnp.zeros((1,), jnp.int32),
               "age": jnp.zeros((1,), jnp.float32),
               "valid": jnp.zeros((1,), jnp.float32)}

    use_ef = wire_ef is not None

    def group_body(carry, p_group, efg=None):
        x, sb, sp, pc, cc, aux_sum = carry
        ef_outs = []
        for j in range(period):

            def apply_j(x, sb, sp, pc, cc, ef, pj=p_group[j], jj=j):
                return _layer_full(
                    pj, cfg, eff_luffy, dist, x, sb, sp, threshold,
                    jj, causal=cfg.causal, enc_out=enc_out,
                    enc_pos=enc_pos, moe_mode=moe_mode, capacity=capacity,
                    plan_carry=pc, cond_carry=cc, wire_ef=ef)

            if cfg.remat:
                apply_j = jax.checkpoint(apply_j)
            efj = efg[j] if efg is not None else None
            x, sb, sp, aux, pc, cc, efo = apply_j(x, sb, sp, pc, cc, efj)
            ef_outs.append(efo)
            aux_sum = jax.tree.map(lambda a, b: a + b, aux_sum, aux)
        ef_stack = jnp.stack(ef_outs) if efg is not None else None
        return (x, sb, sp, pc, cc, aux_sum), ef_stack

    aux0 = moe.MoEAux(*([jnp.float32(0.0)] * moe.N_AUX))
    n_groups = cfg.num_layers // period
    # stack the per-position param lists into a tuple pytree for scan
    stacked = tuple(params["layers"])
    if s_prev0 is None:
        s_prev0 = jnp.zeros((1,), jnp.float32)  # dummy carried value

    # error-feedback xs: the real buffer when enabled, else a structural
    # dummy sliced and discarded (keeps the scan signature uniform)
    ef_xs = wire_ef if use_ef else jnp.zeros((n_groups,), jnp.float32)

    def scan_body(carry, xs):
        p_group, efg = xs
        (x, sb, sp, pc, cc, aux_sum) = carry
        sp_real = sp if use_cond else None
        pc_real = pc if use_reuse else None
        cc_real = cc if use_creuse else None
        (x, sb, sp_new, pc_new, cc_new, aux_sum), ef_y = group_body(
            (x, sb, sp_real, pc_real, cc_real, aux_sum), p_group,
            efg if use_ef else None)
        if not use_cond:
            sp_new = sp
        if not use_reuse:
            pc_new = pc
        if not use_creuse:
            cc_new = cc
        return (x, sb, sp_new, pc_new, cc_new, aux_sum), ef_y

    (x, sideband, s_prev, _pc, _cc, aux_sum), ef_ys = jax.lax.scan(
        scan_body, (x, sideband, s_prev0, pc0, cc0, aux0),
        (stacked, ef_xs))

    sl, sc = chunked_xent(params, cfg, x, sideband["labels"])
    if dist.enabled:
        # global mean over devices happens automatically: sl/sc are global
        pass
    loss = sl / jnp.maximum(sc, 1.0)
    n_moe = sum(1 for i in range(cfg.num_layers) if cfg.ffn_kind(i) == "moe")
    n_moe = max(n_moe, 1)
    aux_mean = jax.tree.map(lambda a: a / n_moe, aux_sum)
    total = loss
    if cfg.uses_moe and cfg.moe is not None:
        total = loss + cfg.moe.router_aux_coef * aux_mean.aux_loss
    metrics = {
        "loss": loss, "aux_loss": aux_mean.aux_loss,
        "dispatch_drop": aux_mean.dispatch_drop,
        "combine_drop": aux_mean.combine_drop,
        "condense_rate": aux_mean.condense_rate,
        "local_frac": aux_mean.local_frac,
        "traffic_before": aux_mean.traffic_before,
        "traffic_after": aux_mean.traffic_after,
        "inter_bytes_flat": aux_mean.inter_bytes_flat,
        "inter_bytes_dedup": aux_mean.inter_bytes_dedup,
        "inter_bytes_shipped": aux_mean.inter_bytes_shipped,
        # plan-reuse ledger (DESIGN.md §9): per-forward COUNTS (sums over
        # MoE sublayers, device-mean), not per-sublayer means — so
        # "plans_built == 1.0" reads as "one full replan this forward"
        "plans_built": aux_sum.plans_built,
        "plans_reused": aux_sum.plans_reused,
        "plan_reuse_mismatch": aux_sum.reuse_mismatch,
        # condensation ledger (DESIGN.md §10): similarity builds per
        # forward + pairs the backend actually measured (sums)
        "measured_pairs": aux_sum.measured_pairs,
        "condense_built": aux_sum.condense_built,
        "condense_reused": aux_sum.condense_reused,
    }
    if use_ef:
        # refreshed residual buffer for the caller to thread into the
        # next step's forward (underscore: stripped before logging)
        metrics["_wire_ef"] = ef_ys
    return total, metrics


def _run_encoder(enc_params, cfg, luffy, dist, enc_x):
    period = pattern_period(cfg)

    def group_body(x, p_group):
        positions = jnp.broadcast_to(
            jnp.arange(x.shape[1], dtype=jnp.int32)[None], x.shape[:2])
        for j in range(period):
            p = p_group[j]
            x, _ = _token_mixer_full(p, cfg, x, positions, j, causal=False,
                                     enc_out=None, enc_pos=None, dist=dist)
            xn = bk.norm_apply(p["ffn_norm"], x, cfg.norm)
            x = x + bk.ffn_apply(p["ffn"], cfg, xn)
        return x, None

    x, _ = jax.lax.scan(group_body, enc_x, tuple(enc_params["layers"]))
    return bk.norm_apply(enc_params["final_norm"], x, cfg.norm)
