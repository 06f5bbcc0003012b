"""Topology-aware communication subsystem (DESIGN.md §5).

Single source of truth for *where bytes go and what they cost*:

* :mod:`repro.comm.topology` — the :class:`Topology` descriptor (nodes ×
  devices-per-node, per-link bandwidth/latency) every other layer prices
  links against;
* :mod:`repro.comm.hierarchical` — two-phase ``hier_all_to_all`` /
  ``hier_combine`` collectives and the :class:`CommContext` the MoE
  layer runs its dispatch/combine through;
* :mod:`repro.comm.ledger` — traced + analytic traffic accounting
  (flat vs per-node-deduplicated inter-node bytes);
* :mod:`repro.comm.compat` — the shared Auto-typed mesh constructor and
  the varying-manual-axes casts the MoE layer needs inside shard_map.
"""
from repro.comm.compat import make_mesh, pmean_all, pvary_all
from repro.comm.hierarchical import (CommContext, hier_all_to_all,
                                     hier_combine)
from repro.comm.ledger import (a2a_time_s, dispatch_bytes,
                               dispatch_node_ledger, expected_dedup_factor,
                               simulate_dispatch_rows)
from repro.comm.topology import Topology, model_axes_of

__all__ = [
    "CommContext", "Topology", "a2a_time_s", "dispatch_bytes",
    "dispatch_node_ledger", "expected_dedup_factor", "hier_all_to_all",
    "hier_combine", "make_mesh", "model_axes_of", "pmean_all", "pvary_all",
    "simulate_dispatch_rows",
]
