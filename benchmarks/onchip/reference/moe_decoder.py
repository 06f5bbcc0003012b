"""Plain float32 reference of the MoE decoder both configurations name:
embedding, pre-norm causal attention (RoPE where the configuration says
so), an MoE layer with top-k routing, token condensation and gated
experts, a final norm, the LM head, cross-entropy plus the router's
load-balance loss, and AdamW with global-norm clipping.

It imports nothing of the program. It follows the semantics the program
defines (LUFFY, arXiv:2411.15419):

* MoE input ``xn = rms(x) * scale``; router softmax over experts, top-k,
  the k gates renormalised; load-balance loss ``E * sum_e f_e p_e`` with
  ``f`` the top-1 share and ``p`` the mean probability over the batch's
  positions.
* Condensation (section V): consecutive groups of ``G`` tokens of a
  sequence, applied only where ``G`` divides the sequence length. Pairs
  with another top-1 expert are dissimilar; the previous MoE layer's
  similarity above ``s1`` counts as similar, below ``s2`` as dissimilar;
  the rest are measured as normalised cosine ``(cos + 1) / 2``. Pairs at
  or above the threshold are joined, each connected component keeps its
  highest-degree token (ties to the lowest index), and every token takes
  its representative's output. The threshold is 0.999 on the first step
  and paper Eq. 2 after.
* Expert rows: each non-padding representative is sent to its top-k
  experts, ``FFN_e(h) = (act(h W_gate) * (h W_up)) W_down`` on
  ``h = rms(x) * scale``; output ``x + sum_k g_k FFN_k`` over the rows
  that reached their expert. An expert takes ``capacity_factor * tokens
  * k * (1 - rate) / E`` rows (rounded up to 8), ``rate`` being the
  condensation rate bucket the step was compiled for; first choices
  before second choices, in token order (GShard); later rows are
  dropped. A token that is not sent keeps ``x``.

No buffers and no collectives: each expert is applied to every token
and weighted by the rows that reached it.

``lower=True`` computes every matrix product, forward and backward,
with operands rounded to int8 under one scale per tensor: the control
that must fail the comparison. The configuration computes in bfloat16;
the next precision down is int8 or fp8, and int8 is the one the v5e's
matrix units run natively (393 TOP/s against 197 TFLOP/s bf16).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
INT8_MAX = 127.0


def _int8(x):
    """Round to int8 under one symmetric scale per tensor, back to f32."""
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
    scale = jnp.where(amax > 0, amax / INT8_MAX, 1.0)
    return jnp.round(x / scale) * scale


@jax.custom_vjp
def _mm_int8(a, b):
    return jnp.matmul(_int8(a), _int8(b), precision=HIGHEST)


def _mm_int8_fwd(a, b):
    qa, qb = _int8(a), _int8(b)
    return jnp.matmul(qa, qb, precision=HIGHEST), (qa, qb)


def _mm_int8_bwd(res, g):
    qa, qb = res
    qg = _int8(g)
    da = jnp.matmul(qg, jnp.swapaxes(qb, -1, -2), precision=HIGHEST)
    db = jnp.matmul(jnp.swapaxes(qa, -1, -2), qg, precision=HIGHEST)
    # sum broadcast batch dims of b back (b is a plain matrix here)
    while db.ndim > qb.ndim:
        db = db.sum(0)
    return da, db


_mm_int8.defvjp(_mm_int8_fwd, _mm_int8_bwd)


def mm(a, b, lower: bool):
    if lower:
        return _mm_int8(a, b)
    return jnp.matmul(a, b, precision=HIGHEST)


def layer_norm(x, g, b, eps=1e-6):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def rms_norm(x, g, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def norm(p, prefix, x, kind):
    if kind == "ln":
        return layer_norm(x, p[prefix + ".scale"], p[prefix + ".bias"])
    return rms_norm(x, p[prefix + ".scale"])


def rope(x, theta=10_000.0):
    """x [B, S, H, hd]; rotation of the two halves of each head."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def act_fn(name):
    return {"gelu": jax.nn.gelu, "silu": jax.nn.silu}[name]


def adaptive_threshold(l_ini, l_prev):
    """Paper Eq. 2."""
    return 1.0 / (1.0 + math.exp((l_ini - l_prev) / max(l_ini, 1e-9)))


# ---------------------------------------------------------------------------
# condensation
# ---------------------------------------------------------------------------

def condense(xn, e0, s_prev, thr, G, s1=0.8, s2=0.2):
    """xn [B, S, d], e0 [B, S] top-1 expert, s_prev [B, S//G, G, G].
    Returns (rep [B, S] index within the sequence, sim like s_prev)."""
    B, S, d = xn.shape
    ng = S // G
    xg = xn.reshape(B, ng, G, d)
    eg = e0.reshape(B, ng, G)
    nrm = xg * jax.lax.rsqrt(jnp.sum(xg * xg, -1, keepdims=True) + 1e-8)
    cos = jnp.einsum("bngd,bnhd->bngh", nrm, nrm, precision=HIGHEST)
    measured = (cos + 1.0) * 0.5
    same = eg[..., :, None] == eg[..., None, :]
    hi, lo = s_prev > s1, s_prev < s2
    sim = jnp.where(same & ~hi & ~lo, measured, 0.0)
    sim = jnp.where(hi & same, 1.0, sim)
    sim = jnp.where(same, sim, 0.0)
    eye = jnp.eye(G, dtype=bool)
    link = ((sim >= thr) & ~eye) | eye
    reach = link.astype(jnp.float32)
    for _ in range(max(1, math.ceil(math.log2(G)))):
        reach = (jnp.matmul(reach, reach, precision=HIGHEST) > 0.5
                 ).astype(jnp.float32)
    idx = jnp.arange(G)
    score = jnp.sum(link, -1) * G + (G - 1 - idx)            # [B, ng, G]
    cand = jnp.where(reach > 0.5, score[..., None, :], -1)
    rep = jnp.argmax(cand, -1) + (jnp.arange(ng) * G)[None, :, None]
    return rep.reshape(B, S), jax.lax.stop_gradient(sim)


# ---------------------------------------------------------------------------
# capacity (GShard order: every token's first choice before any second)
# ---------------------------------------------------------------------------

def capacity(conf, tokens, rate=0.0):
    """Rows each expert takes: ``capacity_factor * tokens * k * (1 -
    rate) / E``, rounded up to a multiple of 8 (at least 8)."""
    c = math.ceil(conf["capacity_factor"] * tokens * conf["top_k"]
                  * (1.0 - rate) / conf["num_experts"])
    return max(8, (c + 7) // 8 * 8)


def dispatched(gi, send, E, cap):
    """gi [B, S, k] experts, send [B, S] tokens sent. Rows take their
    expert's slots in order: every first choice in token order, then
    every second choice; rows past ``cap`` are dropped. Returns [B, S,
    k]: 1 where the row reaches its expert."""
    B, S, k = gi.shape
    e = gi.reshape(B * S, k).T.reshape(k * B * S)
    ok = jnp.broadcast_to(send.reshape(1, B * S), (k, B * S)).reshape(-1)
    oh = jax.nn.one_hot(e, E, dtype=jnp.int32) * ok[:, None]
    pos = jnp.take_along_axis(jnp.cumsum(oh, 0) - oh, e[:, None], 1)[:, 0]
    kept = (ok & (pos < cap)).reshape(k, B * S).T.reshape(B, S, k)
    return kept.astype(jnp.float32)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def _layer(conf, num, lp, x, s_prev, thr, valid):
    """One attention + MoE layer on [B, S, d]. Returns (x, sim, aux)."""
    lower = num["lower"]
    B, S, d = x.shape
    H, hd = conf["num_heads"], conf["head_dim"]
    E, k = conf["num_experts"], conf["top_k"]
    h = norm(lp, "attn_norm", x, conf["norm"])
    q = mm(h, lp["wq"], lower).reshape(B, S, H, hd)
    kk = mm(h, lp["wk"], lower).reshape(B, S, H, hd)
    v = mm(h, lp["wv"], lower).reshape(B, S, H, hd)
    if conf["use_rope"]:
        q, kk = rope(q), rope(kk)
    causal = jnp.tril(jnp.ones((S, S), bool))

    @jax.checkpoint
    def attend(qkv):                       # one sequence: [S, H, hd] each
        qh, kh, vh = (jnp.swapaxes(t, 0, 1) for t in qkv)
        s = mm(qh, jnp.swapaxes(kh, -1, -2), lower) / math.sqrt(hd)
        s = jnp.where(causal, s, -1e30)
        o = mm(jax.nn.softmax(s, -1), vh, lower)
        return jnp.swapaxes(o, 0, 1).reshape(S, H * hd)

    o = jax.lax.map(attend, (q, kk, v))
    x = x + mm(o, lp["wo"], lower)

    xn = rms_norm(x, lp["moe_norm.scale"])
    probs = jax.nn.softmax(mm(xn, lp["router"], lower), -1)   # [B,S,E]
    gv, gi = jax.lax.top_k(probs, k)
    gw = gv / jnp.maximum(jnp.sum(gv, -1, keepdims=True), 1e-9)
    pm = probs.reshape(-1, E).mean(0)
    fm = jax.nn.one_hot(gi[..., 0], E).reshape(-1, E).mean(0)
    aux = E * jnp.sum(fm * pm)

    G = num["group"]
    if s_prev is not None:
        rep, sim = condense(jax.lax.stop_gradient(xn), gi[..., 0], s_prev,
                            thr, G)
        is_rep = rep == jnp.arange(S)[None, :]
    else:
        rep, sim, is_rep = None, None, jnp.ones((B, S), bool)
    send = valid & is_rep                                      # [B, S]
    kept = dispatched(gi, send, E, capacity(conf, B * S, num["rate"]))
    wexp = jnp.sum(jax.nn.one_hot(gi, E) * (gw * kept)[..., None], -2)
    act = act_fn(conf["act"])

    @jax.checkpoint
    def one_expert(delta, e_in):
        wu, wg, wd, we = e_in
        y = mm(act(mm(xn, wg, lower)) * mm(xn, wu, lower), wd, lower)
        return delta + y * we[..., None], None

    delta, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (lp["w_up"], lp["w_gate"], lp["w_down"], jnp.moveaxis(wexp, -1, 0)))
    y = x + delta
    if rep is not None:
        y = jnp.take_along_axis(y, rep[..., None], axis=1)
    return y, sim, aux


def batch_loss(params, tokens, labels, lens, thr, conf, num):
    """Mean cross-entropy over the label tokens plus ``coef *`` the
    layers' mean load-balance loss. Returns (that, mean cross-entropy)."""
    lower = num["lower"]
    B, S = tokens.shape
    d, L = conf["d_model"], conf["num_layers"]
    G = num["group"]
    x = jnp.take(params["embed"], tokens, axis=0) * math.sqrt(d)
    valid = jnp.arange(S)[None, :] < lens[:, None]
    s_prev = (jnp.full((B, S // G, G, G), 0.5, jnp.float32)
              if S % G == 0 else None)
    per_layer = ("attn_norm.scale", "attn_norm.bias", "wq", "wk", "wv",
                 "wo", "moe_norm.scale", "router", "w_up", "w_gate",
                 "w_down")
    aux_sum = 0.0
    for l in range(L):
        lp = {n: params[n][l] for n in per_layer if n in params}
        f = jax.checkpoint(partial(_layer, conf, num))
        x, s_prev, aux = f(lp, x, s_prev, thr, valid)
        aux_sum = aux_sum + aux
    h = norm(params, "final_norm", x, conf["norm"])
    w_out = params["embed"].T if conf["tie_embeddings"] else params["unembed"]
    hf = h.reshape(-1, d)
    lf = labels.reshape(-1)
    # rows per chunk of the LM head: the largest divisor up to 1024
    rows = max(r for r in range(1, 1025) if hf.shape[0] % r == 0)
    n = hf.shape[0] // rows

    @jax.checkpoint
    def ce_chunk(c, inp):
        hc, lc = inp
        lg = mm(hc, w_out, lower)
        lse = jax.nn.logsumexp(lg, -1)
        gold = jnp.take_along_axis(lg, jnp.maximum(lc, 0)[:, None], 1)[:, 0]
        return c + jnp.sum((lse - gold) * (lc >= 0)), None

    ce_sum, _ = jax.lax.scan(ce_chunk, jnp.float32(0.0),
                             (hf.reshape(n, rows, d), lf.reshape(n, rows)))
    ce = ce_sum / jnp.sum(lf >= 0)
    return ce + conf["router_aux_coef"] * (aux_sum / L), ce


# ---------------------------------------------------------------------------
# three steps of AdamW
# ---------------------------------------------------------------------------

def lr_at(opt, step):
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    prog = min(max((step - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0),
               1.0)
    cos = 0.5 * (1.0 + math.cos(math.pi * prog))
    return opt["lr"] * warm * (0.1 + 0.9 * cos)


def leaf_norms(tree) -> Dict[str, jnp.ndarray]:
    """Per leaf, per layer for stacked leaves: ``name[l]`` -> norm."""
    out = {}
    for n, a in tree.items():
        if n in ("embed", "unembed", "final_norm.scale", "final_norm.bias"):
            out[n] = jnp.sqrt(jnp.sum(jnp.square(a)))
        else:
            sq = jnp.sum(jnp.square(a).reshape(a.shape[0], -1), -1)
            for l in range(a.shape[0]):
                out[f"{n}[{l}]"] = jnp.sqrt(sq[l])
    return out


def step_fns(conf, opt, *, lower=False, group=128, rate=0.0):
    """The reference's jitted pieces: ``vg`` (loss and gradient of a
    batch) and ``update`` (clipping and AdamW), state donated where it
    is replaced."""
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    num = {"lower": lower, "group": group, "rate": rate}

    @jax.jit
    def vg(p, tokens, labels, lens, thr):
        return jax.value_and_grad(batch_loss, has_aux=True)(
            p, tokens, labels, lens, thr, conf, num)

    @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def update(p, g, m, v, step, lr):
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
        scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gn, 1e-9))
        g = jax.tree.map(lambda x: x * scale, g)
        norms = leaf_norms(g)
        m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
        v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
        bc1 = 1.0 - b1 ** step
        bc2 = 1.0 - b2 ** step

        def upd(name, pp, mm_, vv):
            dlt = (mm_ / bc1) / (jnp.sqrt(vv / bc2) + eps)
            if "norm" not in name:
                dlt = dlt + wd * pp
            return pp - lr * dlt

        p = {n: upd(n, p[n], m[n], v[n]) for n in p}
        return p, m, v, norms

    return vg, update


def train3(conf, params, batches, opt, *, lower=False, group=128, rate=0.0,
           fault=None):
    """Three AdamW steps from ``params`` on ``batches`` (host dicts of
    tokens/labels/seq_len), with expert capacity for condensation rate
    ``rate``. ``params`` is consumed.

    ``fault`` plants a fault in the reference put in the program's
    place: ``"half_batch"`` trains on the first half of every batch;
    ``"token"`` alters one input token of the first batch.
    Returns dict(losses, grad1 {leaf: norm}, params)."""
    vg, update = step_fns(conf, opt, lower=lower, group=group, rate=rate)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    l_ini = l_prev = None
    losses, grad1 = [], None
    for t, batch in enumerate(batches[:3], start=1):
        b = {k: jnp.asarray(x) for k, x in batch.items()}
        if fault == "half_batch":
            b = {k: x[:x.shape[0] // 2] for k, x in b.items()}
        if fault == "token" and t == 1:
            b["tokens"] = b["tokens"].at[0, 0].set(
                (b["tokens"][0, 0] + 1) % conf["vocab_size"])
        thr = 0.999 if l_ini is None else adaptive_threshold(l_ini, l_prev)
        (_, ce), g = vg(params, b["tokens"], b["labels"], b["seq_len"],
                        jnp.float32(thr))
        loss = float(ce)
        params, m, v, gnorms = update(params, g, m, v, jnp.float32(t),
                                      jnp.float32(lr_at(opt, t)))
        del g
        if t == 1:
            grad1 = {n: float(x) for n, x in gnorms.items()}
        losses.append(loss)
        l_ini = loss if l_ini is None else l_ini
        l_prev = loss
    return {"losses": losses, "grad1": grad1, "params": params}
