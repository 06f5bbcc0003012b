"""The deduplicated hierarchical wire format (DESIGN.md §10).

Since PR 1 the traffic ledger has *priced* a per-node-deduplicated
payload (``inter_bytes_dedup``: a token whose top-k experts land on the
same remote node crosses the expensive link once, not k times) while the
executed hier collectives still moved the dense buffers. This module
actually ships it, behind ``LuffyConfig.hier_dedup``:

**Dispatch.** Each source device packs one *unique* payload row per
(token, destination node) into a ``[N, C_u, d]`` buffer (``C_u`` =
:func:`dedup_capacity`) and a *re-expansion map* — the ordinary dense
``[E, C]`` dispatch layout carrying, per expert row, the unique-slot
pointer and the per-copy gate weight instead of the d-dim payload. The
unique buffer crosses nodes once per (token, node) pair (inter-node
all-to-all over the node axis), then fans out to the destination node's
devices on the cheap links (intra-node all-gather — exactly the
phase-2 redistribution ``repro.comm.ledger.dispatch_bytes(dedup=True)``
models). Row reconstruction through the map is exact, so expert inputs
are **bit-identical** to the dense wire.

**Combine.** Expert outputs destined to the same (source token, node)
are pre-reduced *on the expert node* — a deterministic scatter-add in
fixed row order, then an intra-node reduce-scatter — and one partial row
per (token, node) crosses back. The source adds the per-node partials in
ascending node order, so the whole reduction has a fixed, documented
association ("sum-order-stable"): outputs are deterministic run-to-run,
but associate differently than the flat wire's per-copy sum — dedup mode
matches flat within float tolerance, not bitwise (tested).

Scope: **universal** (DESIGN.md §15). Dispatch is mode-independent —
experts never move, so the (token, node) unique packing is identical
under migration and pipelining. Migrate-mode combine re-addresses rows
to post-migration homes through a *dest-keyed* map: the re-expansion
map carries each row's destination position in the migrated frame
(``dest_gpos``), the expert node pre-reduces per (token, **dest**
device) and one partial row per (token, node) crosses straight to the
token's NEW home (:func:`dedup_combine_migrate`) — same
sum-order-stable schedule, no detour through the source. Pipelined
execution chunks the *unique-row* capacity
(``repro.sched.plan_unique_chunks``): each chunk's inter-node hop is
issued before the previous chunk's intra-node fan-out/dequantize is
consumed (the §6 depth-2 schedule), and chunks reassemble in the sync
layout before reconstruction — bit-identical to the sync dedup wire
(``ExchangePlan.wire`` records the executed format).

**Wire precision (DESIGN.md §14).** Both wires compose with
``LuffyConfig.wire_dtype``: activation rows are quantized
(:mod:`repro.comm.dtypes`) immediately before the node-crossing
collective and dequantized immediately after, so everything downstream
of the hop — fan-out, reconstruction, expert compute — runs at the
compute dtype on identical values to a quantize-then-exchange
reference (casts and per-row block scaling commute with permutation
collectives). The re-expansion map (``mbuf``) and the combine's int32
metadata never quantize: exact slot pointers are what make dedup
reconstruction bit-exact. ``wire_dtype="f32"`` is the identity wire —
byte-for-byte the historical graphs.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.comm import CommContext
from repro.comm import dtypes as wdt
from repro.sched import ChunkPlan, run_pipeline

Array = jnp.ndarray


def _node_hop(q, sc, cdt, d: int, *, comm: CommContext,
              chunks: Optional[ChunkPlan] = None,
              fanout: bool = False) -> Array:
    """Cross the node axis with a quantized ``[N, R, .]`` payload and
    dequantize right after the hop (optionally following with the
    intra-node all-gather fan-out), software-pipelined over unique-row
    chunks when ``chunks`` is given.

    Chunking slices axis 1 (the unique-row axis): quantization is
    per-row, the collective is a permutation, and chunks reassemble by
    concatenation in slot order, so the chunked hop is **bit-identical**
    to the single-shot hop — the §6 depth-2 schedule just lets chunk
    k+1's expensive inter-node transfer fly while chunk k dequantizes
    and fans out on the cheap links.
    """
    def _land(qk, sck):
        x = wdt.dequantize_rows(qk, sck, cdt, d)
        return comm.local_all_gather(x) if fanout else x

    if chunks is None or chunks.n_chunks <= 1:
        q1 = comm.node_all_to_all(q)
        sc1 = None if sc is None else comm.node_all_to_all(sc)
        return _land(q1, sc1)

    def _disp(k):
        o, s = chunks.offsets[k], chunks.sizes[k]
        qk = comm.node_all_to_all(
            jax.lax.slice_in_dim(q, o, o + s, axis=1))
        sck = None if sc is None else comm.node_all_to_all(
            jax.lax.slice_in_dim(sc, o, o + s, axis=1))
        return qk, sck

    outs, _ = run_pipeline(chunks.n_chunks, dispatch=_disp,
                           compute=lambda k, p: _land(*p))
    return jnp.concatenate(outs, axis=1)


def ship_rows(comm_fn, buf: Array, d: int, wire_dtype: str) -> Array:
    """Move a ``[..., w >= d]`` buffer through a permutation collective
    with the activation columns (``[..., :d]``) at the wire dtype.

    The collective only permutes rows across devices, so
    quantize → ship → dequantize is bit-identical to
    quantize → dequantize → ship (the §14 reference-path law the tests
    pin). Trailing columns (gate weight / primary flag, 2 of ``w - d``)
    and the f8 scale sideband ship as separate arrays through the same
    collective at full precision. ``"f32"`` returns the single-buffer
    historical path untouched.
    """
    if wire_dtype == "f32":
        return comm_fn(buf)
    q, sc = wdt.quantize_rows(buf[..., :d], wire_dtype)
    q = comm_fn(q)
    if sc is not None:
        sc = comm_fn(sc)
    x = wdt.dequantize_rows(q, sc, buf.dtype, d)
    if buf.shape[-1] == d:
        return x
    tail = comm_fn(buf[..., d:])
    return jnp.concatenate([x, tail], axis=-1)


def dedup_capacity(tokens: int, e_local: int, local: int,
                   capacity: int) -> int:
    """Static unique-row capacity per (source device, destination node).

    Bounded by both the token count (each token occupies at most one
    unique slot per node) and the node's dispatch slots (a unique row
    exists only if ≥1 of its copies took a slot on that node:
    ``e_local·L·C``), so the packing can never overflow — no drop path.
    """
    bound = min(tokens, e_local * local * capacity)
    return max(8, ((bound + 7) // 8) * 8)


def dedup_dispatch(xf, expert_idx, gate_w, valid, pos, *,
                   comm: CommContext, e_local: int, capacity: int,
                   wire_dtype: str = "f32", use_kernel: bool = False,
                   dest_gpos: Optional[Array] = None,
                   prim: Optional[Array] = None,
                   chunks: Optional[ChunkPlan] = None,
                   ) -> Tuple[Array, Array, Array, Dict]:
    """Ship the deduplicated dispatch payload; reconstruct dense rows.

    xf: [T, d] payload rows (compute dtype); expert_idx/gate_w/valid/
    pos: [T, k] routing (valid already excludes condensed/dropped rows).
    Returns ``(x_rows [E_local, M, C, d], gw [E_local, M, C],
    rvalid [E_local, M, C] bool, state)`` — ``x_rows`` bit-identical to
    the dense wire's payload slabs (at the wire dtype's reconstruction
    when ``wire_dtype != "f32"``); ``state`` carries the maps
    :func:`dedup_combine` needs plus the shipped-bytes ledger count.

    Migrate mode (``dest_gpos``/``prim`` given): the re-expansion map
    grows two planes — each copy's destination global position
    ``dest_gpos [T]`` (``dest_device * T + dest_pos`` in the migrated
    frame) and its primary flag ``prim [T, k]`` — so the expert side
    can re-address the combine (:func:`dedup_combine_migrate`) without
    a second exchange. The payload wire itself is untouched:
    **dispatch is mode-independent** (experts never move), so
    ``x_rows`` stays bit-identical to the vanilla dedup dispatch.

    ``chunks`` pipelines the unique-row node hop (bit-identical
    reassembly, see :func:`_node_hop`).

    ``use_kernel`` routes the hot pre-dispatch path — gate-mask →
    dedup-pack → quantize — through the fused Pallas kernel
    (:func:`repro.kernels.ops.pack_quantize`) instead of the
    scatter-then-quantize pure-jnp composition; the two are bit-equal
    (each unique slot has exactly one contributing token, so gather
    and scatter-add-onto-zeros produce the same values and the codec
    formula is shared).
    """
    N = jax.lax.axis_size(comm.node_axis)
    L = jax.lax.axis_size(comm.local_axis)
    M = N * L
    T, k = expert_idx.shape
    d = xf.shape[1]
    C = capacity
    E = e_local * M
    cdt = xf.dtype
    my_node = comm.index() // L

    node_of = (expert_idx // e_local) // L                  # [T, k]
    # distinct destination nodes per token (the dedup map)
    hit = (node_of[..., None] == jnp.arange(N)[None, None, :]) \
        & valid[..., None]                                  # [T, k, N]
    headed = jnp.any(hit, axis=1)                           # [T, N]
    h_i = headed.astype(jnp.int32)
    urank = jnp.cumsum(h_i, axis=0) - h_i                   # [T, N]
    C_u = dedup_capacity(T, e_local, L, C)
    un_safe = jnp.where(headed, urank, 0)

    # unique payload buffer: one row per (token, dest node), quantized
    # for the wire. Exactly one token heads each occupied slot, so the
    # fused gather-form kernel and the scatter-add-onto-zeros build the
    # same values; empty slots are zero rows (the gate mask) either way.
    n_grid = jnp.broadcast_to(jnp.arange(N)[None, :], (T, N))
    if use_kernel:
        from repro.kernels import ops as kops
        tok_src = jnp.where(
            headed,
            jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[:, None],
                             (T, N)), -1)
        # inverse map: slot -> contributing token (-1 = empty). At most
        # one token per slot, so scatter-max is deterministic.
        tok = jnp.full((N, C_u), -1, jnp.int32).at[n_grid, un_safe].max(
            tok_src, mode="drop")
        q, sc = kops.pack_quantize(xf, tok.reshape(-1),
                                   wire_dtype=wire_dtype)
        q = q.reshape(N, C_u, q.shape[-1])
        if sc is not None:
            sc = sc.reshape(N, C_u, sc.shape[-1])
    else:
        ubuf = jnp.zeros((N, C_u, d), cdt).at[n_grid, un_safe].add(
            xf[:, None, :] * headed[..., None].astype(cdt), mode="drop")
        q, sc = wdt.quantize_rows(ubuf, wire_dtype)

    # re-expansion map in the dense dispatch layout: (uslot+1, gate_w)
    # — plus, in migrate mode, (dest_gpos+1, prim). All planes ride the
    # exact f32 map exchange; dest_gpos < M*T stays far below 2^24, so
    # the f32 round-trip is lossless.
    u_copy = jnp.take_along_axis(urank, node_of, axis=1)    # [T, k]
    e_f = expert_idx.reshape(-1)
    p_f = pos.reshape(-1)
    v_f = valid.reshape(-1)
    e_safe = jnp.where(v_f, e_f, 0)
    p_safe = jnp.where(v_f, p_f, 0)
    cols = [(u_copy + 1).astype(jnp.float32),
            gate_w.astype(jnp.float32)]
    if dest_gpos is not None:
        cols.append(jnp.broadcast_to(
            dest_gpos.astype(jnp.float32)[:, None] + 1.0, (T, k)))
        cols.append(prim.astype(jnp.float32))
    w = len(cols)
    mvals = jnp.stack(cols, -1).reshape(-1, w)
    mbuf = jnp.zeros((E, C, w), jnp.float32).at[e_safe, p_safe].add(
        mvals * v_f[:, None].astype(jnp.float32), mode="drop")

    # wire: map via the ordinary dense exchange (2-4 scalars/row, exact
    # — it carries slot pointers), unique payload inter-node once per
    # (token, node) at the wire dtype (+ f8 scale sideband), dequantized
    # right after the node hop so the cheap-link fan-out and everything
    # downstream sees compute-dtype rows
    mbuf = comm.all_to_all(mbuf)
    ug = _node_hop(q, sc, cdt, d, comm=comm, chunks=chunks,
                   fanout=True)                             # [L*N, C_u, d]

    rmeta = mbuf.reshape(M, e_local, C, w).transpose(1, 0, 2, 3)
    u = jnp.round(rmeta[..., 0]).astype(jnp.int32) - 1      # [E_l, M, C]
    rvalid = u >= 0
    u_safe = jnp.maximum(u, 0)
    gw = (rmeta[..., 1] * rvalid.astype(jnp.float32)).astype(cdt)
    m_ids = jnp.arange(M, dtype=jnp.int32)
    gi = (m_ids % L) * N + (m_ids // L)                     # source row in ug
    gi_b = jnp.broadcast_to(gi[None, :, None], u.shape)
    x_rows = ug[gi_b, u_safe] * rvalid[..., None].astype(cdt)

    occ = jnp.sum(h_i.astype(jnp.float32), axis=0)          # [N]
    state = {"headed": headed, "un_safe": un_safe, "u_safe": u_safe,
             "rvalid": rvalid, "N": N, "L": L, "M": M, "C_u": C_u,
             "T": T, "shipped_rows": jnp.sum(occ) - occ[my_node]}
    if dest_gpos is not None:
        dg = jnp.round(rmeta[..., 2]).astype(jnp.int32) - 1
        state["dgpos"] = jnp.where(rvalid, dg, -1)          # [E_l, M, C]
        state["prim"] = (rmeta[..., 3]
                         * rvalid.astype(jnp.float32)).astype(cdt)
    return x_rows, gw, rvalid, state


def dedup_combine(out_rows, state, *, comm: CommContext,
                  wire_dtype: str = "f32",
                  chunks: Optional[ChunkPlan] = None) -> Array:
    """Return gate-weighted expert outputs to their source tokens with
    per-node pre-reduction.

    out_rows: [E_local, M, C, d] finished (gate-weighted) rows in the
    dense layout. Partial sums per (source token, node) accumulate in
    fixed (expert, source, slot) row order on the expert node, an
    intra-node reduce-scatter completes the node sum, one partial row
    per (token, node) crosses back, and the source adds node partials
    in ascending node index — a fully deterministic association.
    ``chunks`` pipelines the return hop over the unique-row axis
    (bit-identical, :func:`_node_hop`). Returns delta [T, d].
    """
    N, L, M, C_u = state["N"], state["L"], state["M"], state["C_u"]
    rvalid, u_safe = state["rvalid"], state["u_safe"]
    headed, un_safe = state["headed"], state["un_safe"]
    d = out_rows.shape[-1]
    cdt = out_rows.dtype
    T = headed.shape[0]

    m_grid = jnp.broadcast_to(
        jnp.arange(M, dtype=jnp.int32)[None, :, None], u_safe.shape)
    comb = jnp.zeros((M, C_u, d), cdt).at[m_grid, u_safe].add(
        out_rows * rvalid[..., None].astype(cdt), mode="drop")
    # finish the node sum on the cheap links, keeping only my column's
    # source chunk (m = n_src * L + l_src)
    comb = comb.reshape(N, L, C_u, d).transpose(1, 0, 2, 3)
    part = comm.local_psum_scatter(comb)                    # [1, N, C_u, d]
    part = part.reshape(N, C_u, d)
    # per-node partials cross back at the wire dtype; the intra-node
    # reduce-scatter above already ran at the compute dtype
    q, sc = wdt.quantize_rows(part, wire_dtype)
    pback = _node_hop(q, sc, cdt, d, comm=comm, chunks=chunks)
    n_grid = jnp.broadcast_to(jnp.arange(N)[None, :], (T, N))
    g = pback[n_grid, un_safe] * headed[..., None].astype(cdt)
    return jnp.sum(g, axis=1)                               # node order


def dedup_combine_migrate(out_rows, state, *, comm: CommContext,
                          wire_dtype: str = "f32",
                          chunks: Optional[ChunkPlan] = None) -> Array:
    """Dest-keyed combine for the migrated frame (DESIGN.md §15).

    out_rows: [E_local, M, C, d] finished rows — gate-weighted AND
    carrying the primary copy's residual (``y·gw + x·prim``), because
    migrate mode *materializes* the post-block hidden state at the
    token's NEW home rather than adding a delta at the source. Rows
    pre-reduce per (token, **destination** device) keyed by the
    ``dest_gpos`` plane of the re-expansion map: a deterministic
    scatter-add in fixed (expert, source, slot) row order into a
    ``[M, T, d]`` buffer, an intra-node reduce-scatter completing the
    node sum, one partial row per (token, node) crossing straight to
    the destination device — no detour through the source — and node
    partials added in ascending node index: the same sum-order-stable
    association as :func:`dedup_combine`, re-addressed. The migration
    permutation is a bijection on global slots, so each destination
    receives exactly T rows — no capacity bound, no drop path.
    ``chunks`` pipelines the return hop over the token axis
    (bit-identical). Returns y [T, d] in the migrated frame.
    """
    N, L, M, T = state["N"], state["L"], state["M"], state["T"]
    dgpos = state["dgpos"]
    d = out_rows.shape[-1]
    cdt = out_rows.dtype

    live = dgpos >= 0
    dd = jnp.where(live, dgpos // T, 0)                     # dest device
    dp = jnp.where(live, dgpos % T, 0)                      # dest position
    comb = jnp.zeros((M, T, d), cdt).at[dd, dp].add(
        out_rows * live[..., None].astype(cdt), mode="drop")
    # finish the node sum on the cheap links, keeping only my column's
    # destination chunk (dest device = n_dest * L + l_dest)
    comb = comb.reshape(N, L, T, d).transpose(1, 0, 2, 3)
    part = comm.local_psum_scatter(comb)                    # [1, N, T, d]
    part = part.reshape(N, T, d)
    q, sc = wdt.quantize_rows(part, wire_dtype)
    pback = _node_hop(q, sc, cdt, d, comm=comm, chunks=chunks)
    return jnp.sum(pback, axis=0)                           # node order
