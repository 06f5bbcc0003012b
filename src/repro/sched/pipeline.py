"""Double-buffered software pipeline over capacity chunks (DESIGN.md §6).

The schedule is the classic two-slot DMA pipeline (warm up the first
transfer, then issue chunk ``k+1``'s transfer *before* consuming chunk
``k``), lifted from the kernel level to the XLA collective level:

    dispatch[0]
    dispatch[1] ; compute[0] ; combine[0]
    dispatch[2] ; compute[1] ; combine[1]
    ...
                  compute[n-1] ; combine[n-1]

At most two dispatch payloads are live at any point — the one being
consumed and the one in flight — so peak buffer memory is ``2/n`` of the
sync path's. XLA lowers the collectives to async start/done pairs; the
program-order interleaving above gives the latency-hiding scheduler a
compute region to sink each ``done`` past. An optimization barrier
(``jax.lax.optimization_barrier``, which differentiates natively) ties each issued next-chunk payload to the current chunk's payload so the
scheduler cannot "helpfully" defer the next collective until after the
current compute (the same reason the attention path barriers its K/V
gathers; see ``models/transformer.py``).

:func:`pipeline_schedule` returns that issue order as data — the explicit
unrolled variant — so tests and humans can inspect exactly what the
executor traces (:func:`format_schedule` pretty-prints it).
"""
from __future__ import annotations

from typing import Callable, List, NamedTuple, Optional, Tuple

import jax


class Stage(NamedTuple):
    name: str                     # "dispatch" | "compute" | "combine"
    chunk: int


def pipeline_schedule(n_chunks: int, *, with_combine: bool = True
                      ) -> Tuple[Stage, ...]:
    """Issue order of the depth-2 software pipeline over ``n_chunks``.

    Invariants (asserted by tests): every chunk's dispatch precedes its
    compute, which precedes its combine; chunk ``k+1``'s dispatch is
    issued before chunk ``k``'s compute; at most two dispatched payloads
    are outstanding at any point.
    """
    assert n_chunks >= 1, n_chunks
    out: List[Stage] = [Stage("dispatch", 0)]
    for k in range(n_chunks):
        if k + 1 < n_chunks:
            out.append(Stage("dispatch", k + 1))
        out.append(Stage("compute", k))
        if with_combine:
            out.append(Stage("combine", k))
    return tuple(out)


def format_schedule(n_chunks: int, *, with_combine: bool = True) -> str:
    """Human-readable pipeline diagram of :func:`pipeline_schedule`."""
    sched = pipeline_schedule(n_chunks, with_combine=with_combine)
    lines, row = [], []
    for st in sched:
        if st.name == "dispatch" and row:
            lines.append(" ; ".join(row))
            row = []
        row.append(f"{st.name}[{st.chunk}]")
    if row:
        lines.append(" ; ".join(row))
    return "\n".join(f"t{i}: {ln}" for i, ln in enumerate(lines))


def run_pipeline(n_chunks: int, *,
                 dispatch: Callable[[int], object],
                 compute: Callable[[int, object], object],
                 combine: Optional[Callable[[int, object], object]] = None,
                 barrier: bool = True):
    """Trace the pipelined execution of ``n_chunks`` chunks.

    ``dispatch(k)`` issues chunk ``k``'s collective and returns its
    payload (any pytree); ``compute(k, payload)`` consumes it;
    ``combine(k, out)`` optionally runs the return-direction collective.
    Returns ``(computed, combined)`` lists in chunk order (``combined``
    is None when no combine stage is given).

    ``barrier=True`` ties (next payload, current payload) with
    ``optimization_barrier`` right after the next dispatch is issued,
    pinning the double-buffered issue order against XLA reordering. The
    executor follows :func:`pipeline_schedule` exactly — the schedule is
    the spec, this is the interpreter.
    """
    payloads = {}
    computed: List[object] = [None] * n_chunks
    combined: Optional[List[object]] = \
        [None] * n_chunks if combine is not None else None
    for st in pipeline_schedule(n_chunks, with_combine=combine is not None):
        if st.name == "dispatch":
            payloads[st.chunk] = dispatch(st.chunk)
            prev = st.chunk - 1
            if barrier and prev in payloads:
                payloads[st.chunk], payloads[prev] = \
                    jax.lax.optimization_barrier(
                        (payloads[st.chunk], payloads[prev]))
        elif st.name == "compute":
            computed[st.chunk] = compute(st.chunk, payloads.pop(st.chunk))
        else:
            combined[st.chunk] = combine(st.chunk, computed[st.chunk])
        assert len(payloads) <= 2, "double-buffer invariant violated"
    return computed, combined
