"""The condensation decision as data (paper §V; DESIGN.md §10).

The paper builds a DGL similarity graph over all tokens headed to the
same expert and keeps one representative per connected component.
Dynamic graphs don't exist on TPU, so we adapt (see DESIGN.md §3):

* tokens are processed in fixed *condensation groups* of ``G`` tokens
  (consecutive tokens of the local shard) — similarity is a blocked
  ``[G, G]`` problem that maps onto the MXU (Pallas kernel in
  ``repro.kernels.similarity``), measured through the pluggable backend
  registry (:mod:`repro.condense.backends`);
* §V-A's skip rules become masks; connected components + highest-degree
  representative (§V-B) become ``ceil(log2(G))`` rounds of vectorized
  min-label propagation;
* the adaptive threshold (Eq. 2) is computed from the running loss and
  additionally quantized to a *rate bucket* that selects a compiled
  executable with capacity ``C' = ceil(C·(1−rate))``.

:func:`build_condense_plan` freezes one sublayer's decision as a
:class:`CondensePlan` — the record ``build_exchange_plan`` embeds in the
:class:`~repro.plan.ExchangePlan`. Like the migration plan (DESIGN.md
§9), a condense plan can be *reused* across sublayers: the
:class:`CondenseSignature` (the primary-expert assignment the rep map
was built on, per-sequence age/validity) threads through the layer scan,
and ``LuffyConfig.condense_reuse`` revalidates it instead of re-running
the O(G²·d) similarity build. Unlike migration reuse, a revalidated
condense plan is only *bit-identical to a rebuild when the rebuild would
produce the same rep map* (identical duplicate structure, or nothing
condensable); in general reuse trades §V-A freshness for planning time,
bounded by ``condense_reuse_max_age`` sublayers.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.comm.compat import pvary_all
from repro.condense import backends as sim_backends

Array = jnp.ndarray


class CondenseOutput(NamedTuple):
    rep_idx: jnp.ndarray      # [T] int32 — each token's representative (global)
    is_rep: jnp.ndarray       # [T] bool — True if token represents itself
    sim: jnp.ndarray          # [n_groups, G, G] f32 — similarity (for s_prev)
    rate: jnp.ndarray         # [] f32 — fraction of tokens condensed
    measured_pairs: jnp.ndarray = 0.0   # [] f32 — pairs actually measured


class CondenseSignature(NamedTuple):
    """What a carried rep map must revalidate against.

    ``expert`` is the primary-expert assignment the map was built on
    (merged tokens must still share an expert — §V skip rule 1);
    ``age``/``valid`` are per-*sequence* so they migrate with sequences
    under §IV re-homing. ``valid`` is pinned to 0 under
    ``condense_reuse="off"`` so the carry never revalidates while the
    compiled graph stays identical across modes (the graph-parity
    discipline of DESIGN.md §9)."""
    expert: Array             # [T] int32 — expected primary expert per token
    age: Array                # [n_seq] f32 — sublayers since the sim build
    valid: Array              # [n_seq] f32 — 1.0 once a plan was built


class CondenseCarry(NamedTuple):
    """The cross-sublayer reuse state threaded through the layer scan:
    the carried rep map (within-group positions, migration-safe) plus
    its signature fields, flattened per device."""
    rep: Array                # [T] int32 — rep position within the group
    expert: Array             # [T] int32
    age: Array                # [n_seq] f32
    valid: Array              # [n_seq] f32


class CondensePlan(NamedTuple):
    """One sublayer's frozen condensation decision (rides on the
    :class:`~repro.plan.ExchangePlan`). ``backend`` is static; array
    fields are traced. ``signature`` is None on plans built without a
    reuse carry (the historical graph); ``built``/``reused`` feed the
    MoEAux ledger."""
    backend: str
    rep_idx: Array            # [T] int32
    is_rep: Array             # [T] bool
    s_next: Optional[Array]   # [n_groups, G, G] f32 similarity history
    rate: Array               # [] f32
    measured_pairs: Array     # [] f32
    signature: Optional[CondenseSignature] = None
    built: Optional[Array] = None     # [] f32 — 1 when the sim build ran
    reused: Optional[Array] = None    # [] f32 — 1 when the carry was reused


def identity_condense_plan(T: int, backend: str = "exact") -> CondensePlan:
    """The condense-nothing plan (vanilla serving, decode, condensation
    off): every token represents itself."""
    idx = jnp.arange(T, dtype=jnp.int32)
    return CondensePlan(
        backend=backend, rep_idx=idx, is_rep=jnp.ones((T,), bool),
        s_next=None, rate=jnp.float32(0.0),
        measured_pairs=jnp.float32(0.0))


# ---------------------------------------------------------------------------
# Eq. 2 + rate buckets
# ---------------------------------------------------------------------------

def adaptive_threshold(l_ini, l_prev):
    """Paper Eq. (2): h_t = 1 / (1 + exp(l_norm))."""
    l_norm = (l_ini - l_prev) / jnp.maximum(l_ini, 1e-9)
    return 1.0 / (1.0 + jnp.exp(l_norm))


def pick_rate_bucket(threshold: float, sim_quantiles, buckets) -> int:
    """Host-side: choose the largest bucket whose condensable fraction
    (estimated from observed similarity quantiles) is supportable.

    sim_quantiles: callable q -> similarity value at quantile q, or an
    array of per-decile similarity values (len 11, deciles 0..100%).
    """
    import numpy as np
    q = np.asarray(sim_quantiles, dtype=np.float64)
    # fraction of pairs with similarity above threshold
    frac = float(np.mean(q >= threshold))
    best = 0
    for i, b in enumerate(buckets):
        if b <= frac + 1e-9:
            best = i
    return best


# ---------------------------------------------------------------------------
# components + representatives (§V-B)
# ---------------------------------------------------------------------------

def _components_and_reps(adj):
    """adj: [G, G] bool symmetric (no self loops needed). Returns rep [G]
    int32 — the index each node condenses to (highest-degree node of its
    connected component; §V-B).
    """
    G = adj.shape[0]
    idx = jnp.arange(G, dtype=jnp.int32)
    adj = adj | jnp.eye(G, dtype=bool)
    labels = idx
    # min-label propagation; diameter <= G but log2 rounds of
    # squaring-style propagation converge for the clustered graphs we see.
    n_iter = max(1, math.ceil(math.log2(G)) + 1)
    for _ in range(n_iter):
        neigh_min = jnp.min(jnp.where(adj, labels[None, :], G), axis=1)
        labels = jnp.minimum(labels, neigh_min.astype(jnp.int32))
        # propagate through current labels too (pointer jumping)
        labels = labels[labels]
    degree = jnp.sum(adj, axis=1).astype(jnp.int32)
    # highest degree in component, ties -> smallest index
    score = degree * G + (G - 1 - idx)               # larger is better
    same = labels[:, None] == labels[None, :]
    comp_scores = jnp.where(same, score[None, :], -1)
    rep = jnp.argmax(comp_scores, axis=1).astype(jnp.int32)
    return rep


def condense_tokens(x, primary_expert, threshold, *, group_size: int,
                    s_prev: Optional[jnp.ndarray] = None,
                    s1: float = 0.8, s2: float = 0.2,
                    use_kernel: bool = False, backend: str = "exact",
                    lsh_bits: int = 8, lsh_seed: int = 0) -> CondenseOutput:
    """Condense local tokens (paper §V).

    x: [T, d] token embeddings (router input); primary_expert: [T];
    threshold: scalar in [0,1] (runtime value — Eq. 2 or static);
    s_prev: [n_groups, G, G] similarity carried from the previous block;
    backend: similarity-backend registry name (``"exact"`` | ``"lsh"``).

    Returns global rep_idx over [T].
    """
    T, d = x.shape
    G = group_size
    assert T % G == 0, (T, G)
    n_groups = T // G
    xg = x.reshape(n_groups, G, d)
    eg = primary_expert.reshape(n_groups, G)

    def per_group(xb, ebb, spb):
        sim, measured = sim_backends.fast_similarity(
            xb, ebb, spb, s1, s2, use_kernel=use_kernel, backend=backend,
            lsh_bits=lsh_bits, lsh_seed=lsh_seed)
        adj = (sim >= threshold) & ~jnp.eye(G, dtype=bool)
        rep = _components_and_reps(adj)
        return sim, rep, measured

    if s_prev is None:
        sims, reps, measured = jax.vmap(
            lambda a, b: per_group(a, b, None))(xg, eg)
    else:
        sims, reps, measured = jax.vmap(per_group)(
            xg, eg, s_prev.astype(jnp.float32))

    offsets = (jnp.arange(n_groups, dtype=jnp.int32) * G)[:, None]
    rep_idx = (reps + offsets).reshape(T)
    is_rep = rep_idx == jnp.arange(T, dtype=jnp.int32)
    rate = 1.0 - jnp.mean(is_rep.astype(jnp.float32))
    pairs = jnp.sum(measured.astype(jnp.float32)) * float(G * G)
    return CondenseOutput(rep_idx, is_rep, sims, rate, pairs)


def uncondense(y, rep_idx):
    """y: [T, d] MoE outputs (garbage at condensed rows); copy each
    condensed token's value from its representative (token_to_token)."""
    return jnp.take(y, rep_idx, axis=0)


# ---------------------------------------------------------------------------
# plan build + cross-sublayer reuse
# ---------------------------------------------------------------------------

def build_condense_plan(x, primary_expert, threshold, *, group_size: int,
                        s_prev: Optional[Array] = None,
                        s1: float = 0.8, s2: float = 0.2,
                        use_kernel: bool = False, backend: str = "exact",
                        lsh_bits: int = 8, lsh_seed: int = 0,
                        carry: Optional[CondenseCarry] = None,
                        reuse_mode: str = "off",
                        max_age: int = 4) -> CondensePlan:
    """Decide one sublayer's condensation: either a full similarity
    build (:func:`condense_tokens` through the backend registry), or —
    when a threaded ``carry`` revalidates — the carried rep map with the
    similarity history passed through unchanged.

    Revalidation (``reuse_mode="signature"``): the carried map is
    trusted iff it exists, every sequence's age is under ``max_age``,
    and the current primary-expert assignment equals the one it was
    built on (merged tokens must still share an expert). ``"always"``
    skips the expert compare (age bound still applies); ``"off"`` emits
    carries whose valid flag is pinned to 0, so the cond machinery is
    compiled but never fires — keeping "off" and "signature" graphs
    structurally identical (the DESIGN.md §9 graph-parity discipline).

    The reuse machinery needs a similarity history to pass through, so
    it engages only when both ``carry`` and ``s_prev`` are given (the
    layer scan threads both whenever condensation is on); otherwise the
    historical cond-free graph is built.
    """
    T, _ = x.shape
    G = group_size
    e0 = primary_expert.astype(jnp.int32)

    def _full_build():
        return condense_tokens(
            x, e0, threshold, group_size=G, s_prev=s_prev, s1=s1, s2=s2,
            use_kernel=use_kernel, backend=backend, lsh_bits=lsh_bits,
            lsh_seed=lsh_seed)

    reuse_on = reuse_mode != "off"
    if carry is None or s_prev is None:
        out = _full_build()
        sig = None
        if carry is not None:
            # carry threaded but no history to reuse: emit a fixed-shape,
            # never-validating signature so the scan carry stays uniform
            n_seq = carry.age.shape[0]
            sig = CondenseSignature(e0, jnp.zeros((n_seq,), jnp.float32),
                                    jnp.zeros((n_seq,), jnp.float32))
        return CondensePlan(
            backend=backend, rep_idx=out.rep_idx, is_rep=out.is_rep,
            s_next=out.sim, rate=out.rate,
            measured_pairs=out.measured_pairs, signature=sig,
            built=jnp.float32(1.0), reused=jnp.float32(0.0))

    sp3 = s_prev.astype(jnp.float32).reshape(-1, G, G)
    n_seq = carry.age.shape[0]
    have = jnp.all(carry.valid > 0.5)
    fresh = jnp.all(carry.age < jnp.float32(max_age))
    if reuse_mode == "always":
        match = have & fresh
    else:                                   # "off" | "signature"
        match = have & fresh & jnp.all(carry.expert == e0)

    group_base = (jnp.arange(T, dtype=jnp.int32) // G) * G
    # inside shard_map both arms must return the same varying-axes type:
    # every output derives from these inputs, so cast all of them to the
    # union of the inputs' varying axes (a no-op outside shard_map)
    vma = tuple(frozenset().union(*(
        jax.typeof(a).vma for a in jax.tree.leaves((x, e0, sp3, carry)))))

    def _typed(outs):
        return tuple(pvary_all(o, vma) for o in outs)

    def _reuse(_):
        rep_idx = group_base + carry.rep
        is_rep = rep_idx == jnp.arange(T, dtype=jnp.int32)
        rate = 1.0 - jnp.mean(is_rep.astype(jnp.float32))
        return _typed((rep_idx, is_rep, sp3, rate, jnp.float32(0.0)))

    def _build(_):
        out = _full_build()
        return _typed((out.rep_idx, out.is_rep, out.sim, out.rate,
                       out.measured_pairs))

    rep_idx, is_rep, sims, rate, pairs = jax.lax.cond(
        match, _reuse, _build, 0)
    mf = match.astype(jnp.float32)
    age_out = jnp.where(match, carry.age + 1.0, 0.0)
    valid_out = (jnp.ones((n_seq,), jnp.float32) if reuse_on
                 else jnp.zeros((n_seq,), jnp.float32))
    sig = CondenseSignature(e0, age_out, valid_out)
    return CondensePlan(
        backend=backend, rep_idx=rep_idx, is_rep=is_rep, s_next=sims,
        rate=rate, measured_pairs=pairs, signature=sig,
        built=1.0 - mf, reused=mf)


# ---------------------------------------------------------------------------
# host-side stats (bucket selection / Fig. 5)
# ---------------------------------------------------------------------------

def similarity_quantiles(sim, expert_idx=None, same_expert_only: bool = True):
    """Decile values of the off-diagonal similarity distribution (host
    stats for bucket selection / Fig. 5).

    sim: [..., G, G] similarity; expert_idx: [..., G] primary expert ids,
    required when ``same_expert_only`` — only off-diagonal same-expert
    pairs (the pairs condensation can actually merge) enter the
    distribution, not the mostly-zero full matrix. Host-side numpy (the
    selection size is data-dependent, so this is not traceable); returns
    the 11 decile values ``pick_rate_bucket`` consumes.
    """
    import numpy as np
    s = np.asarray(sim, np.float64)
    G = s.shape[-1]
    s = s.reshape(-1, s.shape[-2], G)
    off_diag = ~np.eye(G, dtype=bool)
    if same_expert_only:
        if expert_idx is None:
            raise ValueError(
                "same_expert_only=True needs expert_idx to identify "
                "same-expert pairs (or pass same_expert_only=False)")
        e = np.asarray(expert_idx).reshape(-1, G)
        mask = (e[:, :, None] == e[:, None, :]) & off_diag[None]
    else:
        mask = np.broadcast_to(off_diag[None], s.shape)
    vals = s[mask]
    if vals.size == 0:
        vals = np.zeros((1,), np.float64)
    return np.quantile(vals, np.linspace(0.0, 1.0, 11))
