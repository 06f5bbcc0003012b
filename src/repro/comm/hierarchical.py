"""Hierarchical two-phase collectives (DESIGN.md §5).

``hier_all_to_all`` decomposes a flat all-to-all over the combined
``(node, local)`` axis into an intra-node exchange (cheap links) followed
by an inter-node exchange (expensive links). For chunks laid out
node-major on dim 0 the result is **bit-identical** to
``jax.lax.all_to_all(x, ("node", "local"), 0, 0, tiled=True)`` — the
two-phase path is a drop-in relabeling, so the MoE layer's outputs do not
change when ``comm_mode`` flips.

What does change is the wire profile: every inter-node message now
aggregates the contributions of all ``L`` devices of the source node
(one large message per node pair per phase instead of ``L²`` small
ones). The per-node *payload dedup* (HierMoE-style: a token's payload
crossing once per node, not once per top-k copy) is a separate wire
format: :mod:`repro.condense.wire` ships it behind
``LuffyConfig.hier_dedup`` using the phase collectives below
(``node_all_to_all`` / ``local_all_gather`` / ``local_psum_scatter``);
:mod:`repro.comm.ledger` prices it, and with the dedup wire enabled the
modeled ``inter_bytes_dedup`` equals the bytes actually shipped.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.comm.topology import Topology

AxisName = Union[str, Tuple[str, ...]]


def hier_all_to_all(x, node_axis: str, local_axis: str):
    """Two-phase all-to-all; dim 0 holds one chunk per global device,
    node-major (chunk ``n*L + l`` is headed to device ``(n, l)``).

    Phase 1 (intra-node): exchange over ``local_axis`` keyed on the
    destination-local rank — afterwards device ``(n, l)`` holds, for each
    destination node, everything its node peers want to send to local
    rank ``l`` there. Phase 2 (inter-node): exchange over ``node_axis``
    keyed on the destination node — same-column devices talk, one
    aggregated message per node pair.
    """
    N = jax.lax.axis_size(node_axis)
    L = jax.lax.axis_size(local_axis)
    M = N * L
    assert x.shape[0] % M == 0, (x.shape, N, L)
    chunk = x.shape[0] // M
    b = x.reshape((N, L, chunk) + x.shape[1:])
    # phase 1: dim 1 (dest local rank) -> becomes source local rank
    b = jax.lax.all_to_all(b, local_axis, split_axis=1, concat_axis=1,
                           tiled=True)
    # phase 2: dim 0 (dest node) -> becomes source node
    b = jax.lax.all_to_all(b, node_axis, split_axis=0, concat_axis=0,
                           tiled=True)
    return b.reshape(x.shape)


def hier_combine(x, node_axis: str, local_axis: str):
    """Combine-direction two-phase exchange: aggregate within the node
    first (cheap links), then cross nodes once. As a slot permutation it
    is identical to :func:`hier_all_to_all` (the flat all-to-all is an
    involution, and both phase orders compose to the same global
    transpose), so it is also bit-compatible with the flat path."""
    return hier_all_to_all(x, node_axis, local_axis)


class CommContext(NamedTuple):
    """How the MoE layer should run its expert-parallel collectives.

    ``axes`` are the mesh axes spanning the expert-parallel dimension,
    node-major (("model",) flat, ("node", "local") hierarchical). A
    ``"local"`` context (no axes) is the single-device degenerate case:
    size 1, identity collectives — so executors can hold ONE non-optional
    comm handle instead of special-casing ``comm is None``.
    ``topology`` prices the links; None means uniform/unknown.
    """
    mode: str                           # "flat" | "hier" | "local"
    axes: Tuple[str, ...] = ()
    topology: Optional[Topology] = None

    @classmethod
    def build(cls, mode: str, model_axis: Optional[AxisName],
              topology: Optional[Topology] = None) -> Optional["CommContext"]:
        if model_axis is None:
            return None
        axes = (model_axis,) if isinstance(model_axis, str) \
            else tuple(model_axis)
        if mode == "hier" and len(axes) != 2:
            raise ValueError(
                f"comm_mode='hier' needs a (node, local) model axis pair, "
                f"got {axes}; build the mesh with nodes > 1")
        if mode not in ("flat", "hier"):
            raise ValueError(f"unknown comm_mode {mode!r}")
        return cls(mode, axes, topology)

    @classmethod
    def local(cls, topology: Optional[Topology] = None) -> "CommContext":
        """Single-device context: identity collectives, size 1."""
        return cls("local", (), topology)

    @classmethod
    def ensure(cls, comm: Optional["CommContext"],
               axis_name: Optional[AxisName] = None,
               topology: Optional[Topology] = None) -> "CommContext":
        """Normalize the historical ``(comm, axis_name)`` call boundary to
        one non-optional context: an existing context wins, a bare axis
        name becomes a flat context over it, neither becomes local."""
        if comm is not None:
            return comm
        if axis_name is not None:
            return cls.build("flat", axis_name, topology)
        return cls.local(topology)

    # -- axis arithmetic (shard_map-side) ------------------------------------
    @property
    def axis_name(self) -> Optional[AxisName]:
        if not self.axes:
            return None
        return self.axes[0] if len(self.axes) == 1 else self.axes

    def size(self) -> int:
        if self.mode == "local":
            return 1
        return jax.lax.axis_size(self.axes)

    def index(self):
        if self.mode == "local":
            return 0
        return jax.lax.axis_index(self.axes)

    @property
    def node_axis(self) -> str:
        assert len(self.axes) == 2, self.axes
        return self.axes[0]

    @property
    def local_axis(self) -> str:
        assert len(self.axes) == 2, self.axes
        return self.axes[1]

    # -- collectives ---------------------------------------------------------
    def _fenced(self, exchange, x):
        """Run ``exchange`` between optimization barriers, so that flat
        and hier hand the surrounding compute the same opaque array.
        Unfenced, the TPU compiler folds hier's (node, local) reshape
        into neighbouring reductions and sums in another order: the
        values match flat's to the last ulp only in the forward pass,
        and training drifts apart from the first update."""
        x = jax.lax.optimization_barrier(x)
        return jax.lax.optimization_barrier(exchange(x))

    def all_to_all(self, x):
        """Dispatch-layout exchange: dim 0 = one chunk per device."""
        if self.mode == "local":
            return x
        if self.mode == "hier":
            return self._fenced(lambda b: hier_all_to_all(
                b, self.node_axis, self.local_axis), x)
        return self._fenced(lambda b: jax.lax.all_to_all(
            b, self.axis_name, split_axis=0, concat_axis=0, tiled=True), x)

    def combine(self, x):
        """Combine-layout exchange (same chunk convention)."""
        if self.mode == "local":
            return x
        if self.mode == "hier":
            return self._fenced(lambda b: hier_combine(
                b, self.node_axis, self.local_axis), x)
        return self._fenced(lambda b: jax.lax.all_to_all(
            b, self.axis_name, split_axis=0, concat_axis=0, tiled=True), x)

    # -- single-phase collectives (the dedup wire, repro.condense.wire) ------
    def node_all_to_all(self, x):
        """Inter-node exchange only: dim 0 = one chunk per NODE."""
        assert self.mode == "hier", self.mode
        return jax.lax.all_to_all(x, self.node_axis, split_axis=0,
                                  concat_axis=0, tiled=True)

    def local_all_gather(self, x):
        """Cheap-link fan-out: gather dim 0 across the node's devices."""
        assert self.mode == "hier", self.mode
        return jax.lax.all_gather(x, self.local_axis, axis=0, tiled=True)

    def local_psum_scatter(self, x):
        """Cheap-link reduction: sum across the node's devices, each
        keeping its dim-0 slice (dim 0 must be ``L`` chunks)."""
        assert self.mode == "hier", self.mode
        return jax.lax.psum_scatter(x, self.local_axis,
                                    scatter_dimension=0, tiled=True)

    def link_cost(self) -> Optional[jnp.ndarray]:
        """[M, M] f32 link-cost matrix for the migration planner, or
        None for uniform topologies (planners then use 1 - I)."""
        if self.topology is None or not self.topology.hierarchical:
            return None
        return jnp.asarray(self.topology.link_cost(), jnp.float32)
